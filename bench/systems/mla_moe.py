"""One chip's share of an expert-parallel deployment of a latent-attention
mixture-of-experts engine (DeepSeek-V2), with weights drawn from the seed.

The configuration names the catalogue engine and states the share: the
first ``layers`` layers of the model (a pipeline stage) and, of each
expert layer, the ``n_routed_experts`` experts from ``first_held_expert``
on (the published router width is under ``published``).  The program's
``ModelConfig`` of the share is the registry's config of ``arch`` with
that depth and that range of held experts (``model_config``), handed to
``build_engine``; requests go the way of ``bench/systems/engine.py``:
placed by the SynergAI estimator, generated by
``InferenceEngine.generate``.

``compare`` runs the sampled rows through
``bench/reference/deepseek_v2.py``; of the gaps by which each served
token's logit lies below the reference's best logit at its position, the
limits hold the mean and the share of tokens off the reference's best.
"""

from __future__ import annotations

import dataclasses
import re

from bench.systems import engine

SPANS = engine.SPANS
# named scopes of the program's MLA and expert layers (models/layers.py)
SCOPES = ("mla.project", "mla.latent_attend", "mla.attend", "moe.route",
          "moe.experts", "moe.shared")

# configuration keys and the program's ModelConfig fields they fix
WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "n_heads",
          "intermediate_size": "d_ff", "vocab_size": "vocab",
          "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
          "first_k_dense_replace": "n_dense_layers"}
MLA = {"q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
       "qk_nope_head_dim": "qk_nope_head_dim",
       "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim"}
MOE = {"moe_intermediate_size": "d_ff_expert",
       "num_experts_per_tok": "top_k", "n_shared_experts": "n_shared",
       "n_group": "n_group", "topk_group": "topk_group",
       "norm_topk_prob": "norm_topk_prob",
       "routed_scaling_factor": "routed_scaling_factor"}
YARN = {"factor": "factor",
        "original_max_position_embeddings": "original_max_position",
        "beta_fast": "beta_fast", "beta_slow": "beta_slow",
        "mscale": "mscale", "mscale_all_dim": "mscale_all_dim"}


def model_config(cfg: dict, reduced: bool = False):
    """The program's config of the share the configuration states: the
    registry's (``reduced``: its tiny CPU version's) with the share's
    depth and held experts, prefilling at most the configuration's
    ``prefill_tokens`` tokens at a time; raises where a width the
    configuration states differs."""
    from repro.configs.base import reduced as reduced_config
    from repro.configs.registry import get_config

    pcfg = get_config(cfg["arch"])
    if reduced:
        pcfg = reduced_config(pcfg)
    pcfg = dataclasses.replace(
        pcfg, n_layers=int(cfg["layers"]),
        prefill_tokens=int(cfg.get("prefill_tokens", 0)),
        moe=dataclasses.replace(pcfg.moe,
                                held_start=int(cfg["first_held_expert"]),
                                n_held=int(cfg["n_routed_experts"])))
    published = cfg["published"]["n_routed_experts"]
    checks = [(k, cfg[k], getattr(pcfg, f)) for k, f in WIDTHS.items()]
    checks += [(k, cfg[k], getattr(pcfg.mla, f)) for k, f in MLA.items()]
    checks += [(k, cfg[k], getattr(pcfg.moe, f)) for k, f in MOE.items()]
    checks += [(k, cfg["rope_scaling"][k], getattr(pcfg.rope_scaling, f))
               for k, f in YARN.items()]
    checks += [("published n_routed_experts", published,
                pcfg.moe.n_experts),
               ("torch_dtype", cfg["torch_dtype"], pcfg.dtype)]
    for key, want, got in checks:
        if (want != got if isinstance(want, str)
                else float(want) != float(got)):
            raise ValueError(f"the program's {cfg['arch']} has {got!r} "
                             f"where the configuration's {key} is {want!r}")
    return pcfg


class System(engine.System):
    def __init__(self, cfg: dict, traffic: dict, seed: int, emit,
                 reduced: bool = False):
        from repro.core.offline import characterize
        from repro.launch.serve import build_engine, engine_spec
        from repro.serving import sampling

        self.emit = emit
        self.cfg = cfg
        self.traffic = traffic
        arch = cfg["arch"]
        pcfg = model_config(cfg, reduced)
        self.spec = engine_spec(arch)
        if self.spec.name != cfg["engine"]:
            raise ValueError(f"the catalogue engine for {arch} is "
                             f"{self.spec.name}, not {cfg['engine']}")
        self.vocab = int(pcfg.vocab)
        self.wseed = engine.weight_seed(seed)
        self.cd = characterize()
        self.eng = build_engine(arch, seed=self.wseed, cfg=pcfg,
                                max_len=int(traffic["max_len"]))
        self.clock = engine.FirstTokenClock(sampling.greedy)
        self.eng.sampler = self.clock
        self.n = 0
        self.positions = 0

    def expert_load(self) -> dict:
        """The engine's expert counters so far (``EngineStats``)."""
        s = self.eng.stats
        return {"held_assignments": dict(s.held_assignments),
                "experts_touched": dict(s.experts_touched)}

    def scope_map(self) -> dict:
        """{"decode": instruction name -> named scope} of the engine's
        decode step as compiled (the window's executable again, from
        JAX's compilation caches)."""
        import jax
        import jax.numpy as jnp

        eng = self.eng
        shape = lambda t: jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding), t)
        params = shape(eng.params)
        chip = jax.tree.leaves(eng.params)[0].sharding
        B = int(self.traffic["requests"]["batch"])
        put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                             sharding=chip)
        caches = jax.tree.map(put, jax.eval_shape(
            lambda: eng.model.init_cache(B, eng.max_len)))
        step = {"token": put(jax.ShapeDtypeStruct((B, 1), jnp.int32)),
                "pos": put(jax.ShapeDtypeStruct((), jnp.int32))}
        return {"decode": hlo_scopes(eng._decode.lower(
            params, caches, step).compile().as_text())}

    def compare(self, served, control: bool = False) -> dict:
        """``served``: (request, tokens) pairs of the sampled rows.  Of
        the served tokens' gaps below the reference's best logit: the
        widest, the mean, and the share of tokens off the reference's
        best (a gap above 0); with ``control`` the same of the tokens the
        fp8 control puts first.  The mean and the share see rounding at
        every position; the widest gap is set by the few positions where
        rounding flips a near-tied expert choice, and an expert's weight
        (16 times its score) makes such a flip move the logits far, in
        bf16 as in fp8, so the widest gap is reported but not limited."""
        import numpy as np

        from bench.reference.deepseek_v2 import served_gaps

        gaps, cgaps = served_gaps(self.cfg, self.wseed,
                                  [r.prompts for r, _ in served],
                                  [t for _, t in served], control=control,
                                  positions=self.positions)
        flat = np.concatenate([g.ravel() for g in gaps])
        widest = float(flat.max())
        out = {"requests_compared": len(served),
               "rows_compared": int(sum(t.shape[0] for _, t in served)),
               "tokens_compared": int(flat.size),
               "logit_gap_max": widest,
               "logit_gap_mean": float(flat.mean()),
               "off_best_share": float((flat > 0).mean())}
        if control:
            cflat = np.concatenate([g.ravel() for g in cgaps])
            out["control_logit_gap_max"] = float(cflat.max())
            out["control_logit_gap_mean"] = float(cflat.mean())
            out["control_off_best_share"] = float((cflat > 0).mean())
        self.emit(f"compared {out['tokens_compared']} served tokens of "
                  f"{out['rows_compared']} rows of {len(served)} requests: "
                  f"widest gap {widest!r}")
        return out


_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.*)$", re.M)
_OP_NAME = re.compile(r"op_name=\"([^\"]*)\"")
_REF = re.compile(r"%([\w.\-]+)")


def hlo_scopes(hlo: str) -> dict:
    """Instruction name -> scope, for the instructions of the optimized
    HLO text ``hlo`` that belong to one of ``SCOPES``: the innermost of
    them in the instruction's op_name (fusions carry the op_name of their
    root), or else, where the compiler made the instruction without one
    (the TPU's kernel for ``lax.ragged_dot``, the copy of a layer's
    weights or cache that such a kernel or loop reads), the scope that
    every instruction using it has."""
    out, users = {}, {}
    instrs = _INSTR.findall(hlo)
    defined = {name for name, _ in instrs}
    for name, rest in instrs:
        op = _OP_NAME.search(rest)
        hit = [p for p in op.group(1).split("/") if p in SCOPES] if op \
            else []
        if hit:
            out[name] = hit[-1]
        for ref in set(_REF.findall(rest)) - {name}:
            users.setdefault(ref, set()).add(name)
    changed = True
    while changed:
        changed = False
        for name, us in users.items():
            if name in out or name not in defined:
                continue
            got = {out.get(u) for u in us}
            if len(got) == 1 and None not in got:
                out[name] = got.pop()
                changed = True
    return out


def programs(cfg: dict, traffic: dict, chip):
    """The device programs of a cell, compiled for the described chip
    ``chip`` (by ``bench/compile_check.py``): the engine's prefill of the
    mix's rows at each prompt length, its decode step over the mix's
    cache buffers, and the reference's dense and expert layers at the
    longest compared sequence, plain and as the fp8 control.  Yields
    (what, compiled)."""
    import jax
    import jax.numpy as jnp

    from bench.reference import deepseek_v2 as ref
    from bench.run import Parts
    from repro.models.registry import build_model

    model = build_model(model_config(cfg))
    put = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), t)
    params = put(jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    reqs = Parts(cfg, traffic).requests.make(traffic["requests"], 0,
                                             cfg["vocab_size"])
    B = reqs[0].batch
    for S in sorted({r.prompt_len for r in reqs}):
        tok = jax.ShapeDtypeStruct((B, S), jnp.int32, sharding=chip)
        c = jax.jit(model.prefill).lower(params, {"tokens": tok}).compile()
        yield f"prefill {B}x{S}", c
    cache = put(jax.eval_shape(lambda: model.init_cache(
        B, traffic["max_len"])))
    step = {"token": jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=chip),
            "pos": jax.ShapeDtypeStruct((), jnp.int32, sharding=chip)}
    c = jax.jit(model.decode, donate_argnums=(1,)).lower(
        params, cache, step).compile()
    yield f"decode {B}x{traffic['max_len']}", c
    dims = ref.Dims.of(cfg)
    rows = int(traffic["sample_rows"])
    T = max(r.prompt_len for r in reqs) + ref.padded_positions(
        max(r.out for r in reqs)) - 1
    for kind in ("dense", "moe"):
        w = put(jax.eval_shape(lambda: ref._layer_weights(
            jax.random.PRNGKey(0), dims, kind)))
        x = jax.ShapeDtypeStruct((rows, T, dims.d), jnp.float32,
                                 sharding=chip)
        for q in (False, True):
            c = ref._layer.lower(x, w, dims, kind, q).compile()
            yield f"reference {kind} layer {rows}x{T} fp8={q}", c
