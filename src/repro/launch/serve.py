"""Serving launcher: ``python -m repro.launch.serve --arch <id>``.

Brings up one engine replica of the selected architecture at its
published widths (``--reduced`` swaps in the tiny same-family config the
CPU tests and examples use), with weights drawn from ``--seed`` on the
device under one ``jit``, and answers a few synthetic requests: each is
placed by the SynergAI estimator (Eq. 1-4 against the offline
Configuration Dictionary) and generated by ``InferenceEngine``.  Batch,
prompt length and cache length default to the engine catalogue's
``microbatch``, ``prefill_len`` and ``prefill_len + decode_len``.

    python -m repro.launch.serve --arch qwen3-4b               # on a chip
    python -m repro.launch.serve --arch qwen3-4b --reduced     # on CPU
"""

from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs.base import ModelConfig
from repro.configs.base import reduced as reduced_config
from repro.configs.registry import ARCHS, get_config
from repro.core.engines import EngineSpec, default_engines
from repro.core.estimator import candidate_order, estimate_matrix
from repro.core.job import Job
from repro.core.offline import characterize
from repro.launch.compile_cache import enable_compile_cache
from repro.models.registry import build_model
from repro.serving.engine import InferenceEngine

WORKERS = ["cloud-pod", "edge-large", "edge-small"]


def engine_spec(arch: str) -> EngineSpec:
    """The catalogue engine serving ``arch`` (its bf16 variant when there
    are several); an uncatalogued arch gets the catalogue's defaults."""
    specs = [e for e in default_engines().values() if e.arch == arch]
    specs.sort(key=lambda e: e.precision != "bf16")
    return specs[0] if specs else EngineSpec(f"{arch}/bf16", arch)


def build_engine(arch: str, *, reduced: bool = False, seed: int = 0,
                 max_len: int | None = None,
                 cfg: ModelConfig | None = None) -> InferenceEngine:
    """One replica: seeded weights made on the device by one jitted init,
    so no host copy of the parameters ever exists.  ``cfg`` replaces the
    registry's config of ``arch`` (a chip's share of it, say)."""
    cfg = cfg or get_config(arch)
    if reduced:
        cfg = reduced_config(cfg)
    spec = engine_spec(arch)
    model = build_model(cfg)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(seed))
    return InferenceEngine(model, params,
                           max_len=max_len or spec.prefill_len
                           + spec.decode_len)


@dataclasses.dataclass
class Served:
    rid: int
    worker: str
    plan: str
    batch: dict           # the request's model inputs
    tokens: jax.Array     # [B, gen] generated tokens


def request_batch(cfg, key, B: int, S: int) -> dict:
    """Synthetic model inputs for one request of ``B`` prompts of ``S``
    tokens (plus the stub modality embeddings where the family has
    them)."""
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab)}
    if cfg.family == "vlm":
        batch["vision_embeds"] = 0.02 * jax.random.normal(
            key, (B, cfg.vision.n_vision_tokens, cfg.d_model))
    if cfg.family == "audio":
        batch["audio_embeds"] = 0.02 * jax.random.normal(
            key, (B, S, cfg.d_model))
    return batch


def serve(eng: InferenceEngine, arch: str, *, requests: int = 3,
          gen: int = 16, batch: int | None = None,
          prompt_len: int | None = None, seed: int = 1, cd=None,
          emit=print) -> list[Served]:
    """Place and generate ``requests`` synthetic requests."""
    spec = engine_spec(arch)
    B = batch or spec.microbatch
    S = prompt_len or spec.prefill_len
    cfg = eng.model.cfg
    cd = cd or characterize()
    cataloged = spec.name in default_engines()
    key = jax.random.PRNGKey(seed)
    out = []
    for rid in range(requests):
        worker, plan = "local", "local"
        if cataloged:
            job = Job(rid, spec.name, queries=100, t_qos=120.0,
                      arrival=0.0)
            order = candidate_order(
                estimate_matrix(cd, [job], WORKERS, now=0.0), 0)
            worker = WORKERS[order[0]] if order else "cloud-pod"
            ent = cd.optimal(spec.name, worker)
            plan = f"{worker} (c*={ent.mode}/r{ent.chips_per_replica})"
        key, sub = jax.random.split(key)
        inputs = request_batch(cfg, sub, B, S)
        toks = eng.generate(inputs, gen)
        emit(f"req {rid} [{spec.name}] -> {plan}: prompt {B}x{S}, "
             f"generated {toks.shape[1]} tokens x batch {toks.shape[0]}")
        out.append(Served(rid, worker, plan, inputs, toks))
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (2 layers, d=64, "
                         "float32) for CPU runs")
    ap.add_argument("--batch", type=int, default=None,
                    help="prompts per request (default: the engine's "
                         "microbatch)")
    ap.add_argument("--prompt-len", type=int, default=None,
                    help="default: the engine's prefill_len")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    spec = engine_spec(args.arch)
    S = args.prompt_len or spec.prefill_len
    eng = build_engine(args.arch, reduced=args.reduced, seed=args.seed,
                       max_len=max(S + args.gen, spec.prefill_len
                                   + spec.decode_len))
    serve(eng, args.arch, requests=args.requests, gen=args.gen,
          batch=args.batch, prompt_len=S, seed=args.seed + 1)
    s = eng.stats
    print(f"stats: prefill {s.prefill_tokens} tok, decoded "
          f"{s.decoded_tokens} tok over {s.batches} batches")


if __name__ == "__main__":
    main()
