"""Generic grouped decoder stack.

Layers are described by a per-layer ``LayerSpec`` (a MoE config's first
``n_dense_layers`` are dense); consecutive identical
specs are stacked (leading ``n`` dim) and executed with ``lax.scan`` so the
HLO stays one-layer-sized regardless of depth (compile-time critical for the
512-device dry-run).  Heterogeneous stacks (VLM cross-attn every 5th layer,
Hymba's 3 global-attention layers) become multiple scan groups.

Decode carries each group's stacked cache ([n, B, ...] leaves) through
the scan and hands layer ``i`` a ``layers.StackedLayer`` view of each
leaf.  A sequence buffer (attention k/v, sliding-window rings, MLA
ckv/krope) takes its one new position at (i, :, pos) in the carried
stack, and attention reads layer ``i`` of the written stack; a recurrent
state (rwkv, mamba) replaces its layer whole; a cross-attention cache is
read and carried on untouched; a MoE layer adds the step's expert
counts (``moe_load``, see ``expert_load``) to its row.  The stack the scan ends with is the
decode step's output, so under the engine's donation the cache is
written where it lies and never copied.

Off a mesh, the inference scans (prefill and decode) likewise take a MoE
group's held-expert stacks out of the scanned parameters and hand layer
``i`` ``StackedLayer`` views of them, which the dropless expert layer's
grouped products read in place (``layers._grouped_weights``).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from repro.configs.base import ModelConfig
from repro.models import common, layers
from repro.models.common import rms_norm

# kinds: dense | moe | rwkv | hymba | cross | encdec_dec


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    kind: str
    window: Optional[int] = None
    mla: bool = False


@dataclasses.dataclass(frozen=True)
class Group:
    spec: LayerSpec
    n: int


def build_layout(cfg: ModelConfig) -> list[Group]:
    specs: list[LayerSpec] = []
    for i in range(cfg.n_layers):
        window = cfg.sliding_window
        if cfg.global_layers and i in cfg.global_layers:
            window = None
        if cfg.family == "ssm":
            specs.append(LayerSpec("rwkv"))
        elif cfg.family == "hybrid":
            specs.append(LayerSpec("hymba", window=window))
        elif cfg.family == "audio":
            specs.append(LayerSpec("encdec_dec"))
        elif cfg.family == "vlm" and cfg.vision and (
                i % cfg.vision.cross_attn_every == cfg.vision.cross_attn_every - 2):
            # cross layers at 3, 8, 13, ... for every=5
            specs.append(LayerSpec("cross"))
        elif cfg.moe is not None and i >= cfg.n_dense_layers:
            specs.append(LayerSpec("moe", window=window, mla=cfg.mla is not None))
        else:
            specs.append(LayerSpec("dense", window=window,
                                   mla=cfg.mla is not None))
    groups: list[Group] = []
    for s in specs:
        if groups and groups[-1].spec == s:
            groups[-1] = Group(s, groups[-1].n + 1)
        else:
            groups.append(Group(s, 1))
    return groups


# ----------------------------------------------------------------------------
# per-layer init / forward by kind


def _init_layer(key, cfg: ModelConfig, spec: LayerSpec, dtype):
    ks = jax.random.split(key, 4)
    D = cfg.d_model
    p = {"ln1": jnp.zeros((D,), dtype=dtype)}
    if spec.kind == "rwkv":
        p.update(layers.init_rwkv_layer(ks[0], cfg, dtype))
        p["ln2"] = jnp.zeros((D,), dtype=dtype)
        return p
    if spec.kind == "hymba":
        p["attn"] = layers.init_attention(ks[0], cfg, dtype)
        p["mamba"] = layers.init_mamba(ks[1], cfg, dtype)
        p["norm_attn"] = jnp.zeros((D,), dtype=dtype)
        p["norm_ssm"] = jnp.zeros((D,), dtype=dtype)
        p["ln2"] = jnp.zeros((D,), dtype=dtype)
        p["mlp"] = common.init_mlp(ks[2], D, cfg.d_ff, dtype)
        return p
    if spec.kind == "cross":
        p["attn"] = layers.init_cross_attention(ks[0], cfg, dtype, gated=True)
        p["ln2"] = jnp.zeros((D,), dtype=dtype)
        p["mlp"] = common.init_mlp(ks[1], D, cfg.d_ff, dtype)
        return p
    if spec.kind == "encdec_dec":
        p["attn"] = layers.init_attention(ks[0], cfg, dtype)
        p["ln_cross"] = jnp.zeros((D,), dtype=dtype)
        p["cross"] = layers.init_cross_attention(ks[1], cfg, dtype, gated=False)
        p["ln2"] = jnp.zeros((D,), dtype=dtype)
        p["mlp"] = common.init_mlp(ks[2], D, cfg.d_ff, dtype)
        return p
    # dense / moe
    if spec.mla:
        p["attn"] = layers.init_mla(ks[0], cfg, dtype)
    else:
        p["attn"] = layers.init_attention(ks[0], cfg, dtype)
    p["ln2"] = jnp.zeros((D,), dtype=dtype)
    if spec.kind == "moe":
        p["moe"] = layers.init_moe(ks[1], cfg, dtype)
    else:
        p["mlp"] = common.init_mlp(ks[1], D, cfg.d_ff, dtype)
    return p


def _init_layer_cache(cfg: ModelConfig, spec: LayerSpec, batch, buf_len,
                      ctx_len, dtype):
    buf = min(buf_len, spec.window) if spec.window else buf_len
    if spec.kind == "rwkv":
        return layers.init_rwkv_cache(cfg, batch, dtype)
    if spec.kind == "hymba":
        return {"attn": layers.init_attn_cache(cfg, batch, buf, dtype),
                "mamba": layers.init_mamba_cache(cfg, batch, dtype)}
    if spec.kind == "cross":
        K, hd = cfg.n_kv_heads, cfg.head_dim
        return {"ck": jnp.zeros((batch, ctx_len, K, hd), dtype=dtype),
                "cv": jnp.zeros((batch, ctx_len, K, hd), dtype=dtype)}
    if spec.kind == "encdec_dec":
        K, hd = cfg.n_kv_heads, cfg.head_dim
        return {"attn": layers.init_attn_cache(cfg, batch, buf, dtype),
                "cross": {"ck": jnp.zeros((batch, ctx_len, K, hd), dtype=dtype),
                          "cv": jnp.zeros((batch, ctx_len, K, hd), dtype=dtype)}}
    if spec.mla:
        cache = layers.init_mla_cache(cfg, batch, buf, dtype)
    else:
        cache = layers.init_attn_cache(cfg, batch, buf, dtype)
    if spec.kind == "moe":
        cache["moe_load"] = layers.init_moe_load()
    return cache


def _layer_forward(p, cfg: ModelConfig, spec: LayerSpec, x, *, mode, cache,
                   pos, ctx):
    cache = cache or {}
    if spec.kind == "rwkv":
        cache = layers.read_layer(cache)
        h, tm_cache = layers.rwkv_time_mix(
            p, cfg, rms_norm(x, p["ln1"], cfg.norm_eps), mode=mode,
            cache=cache)
        x = x + h
        h, cm_shift = layers.rwkv_channel_mix(
            p, cfg, rms_norm(x, p["ln2"], cfg.norm_eps), mode=mode,
            cache=cache.get("cm_shift"))
        x = x + h
        new_cache = None
        if mode != "train":
            new_cache = dict(tm_cache, cm_shift=cm_shift)
        return x, new_cache

    if spec.kind == "hymba":
        xin = rms_norm(x, p["ln1"], cfg.norm_eps)
        a, a_cache = layers.attn_sublayer(
            p["attn"], cfg, xin, mode=mode, cache=cache.get("attn"),
            pos=pos, window=spec.window)
        s, s_cache = layers.mamba_branch(
            p["mamba"], cfg, xin, mode=mode,
            cache=layers.read_layer(cache.get("mamba")))
        h = 0.5 * (rms_norm(a, p["norm_attn"], cfg.norm_eps)
                   + rms_norm(s, p["norm_ssm"], cfg.norm_eps))
        x = x + h
        h = common.mlp(p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg.act)
        x = x + h
        new_cache = None
        if mode != "train":
            new_cache = {"attn": a_cache, "mamba": s_cache}
        return x, new_cache

    if spec.kind == "cross":
        h, c_cache = layers.cross_sublayer(
            p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps), mode=mode,
            cache=cache or None, ctx=ctx)
        x = x + jnp.tanh(p["attn"]["gate_attn"]) * h
        h = common.mlp(p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg.act)
        x = x + jnp.tanh(p["attn"]["gate_ffn"]) * h
        return x, (c_cache if mode != "train" else None)

    if spec.kind == "encdec_dec":
        h, a_cache = layers.attn_sublayer(
            p["attn"], cfg, rms_norm(x, p["ln1"], cfg.norm_eps), mode=mode,
            cache=cache.get("attn"), pos=pos, window=None)
        x = x + h
        h, c_cache = layers.cross_sublayer(
            p["cross"], cfg, rms_norm(x, p["ln_cross"], cfg.norm_eps),
            mode=mode, cache=cache.get("cross"), ctx=ctx)
        x = x + h
        h = common.mlp(p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg.act)
        x = x + h
        new_cache = None
        if mode != "train":
            new_cache = {"attn": a_cache, "cross": c_cache}
        return x, new_cache

    # dense / moe
    xin = rms_norm(x, p["ln1"], cfg.norm_eps)
    if spec.mla:
        h, a_cache = layers.mla_sublayer(p["attn"], cfg, xin, mode=mode,
                                         cache=cache or None, pos=pos)
    else:
        h, a_cache = layers.attn_sublayer(p["attn"], cfg, xin, mode=mode,
                                          cache=cache or None, pos=pos,
                                          window=spec.window)
    x = x + h
    xin = rms_norm(x, p["ln2"], cfg.norm_eps)
    if spec.kind == "moe":
        h, load = layers.moe_ffn(p["moe"], cfg, xin)
        if mode == "prefill":
            a_cache["moe_load"] = layers.init_moe_load().at[0].set(load)
        elif mode == "decode":
            a_cache["moe_load"] = layers.count_decode_load(
                cache["moe_load"], load)
    else:
        h = common.mlp(p["mlp"], xin, cfg.act)
    x = x + h
    return x, (a_cache if mode != "train" else None)


# ----------------------------------------------------------------------------
# decoder-level init / forward


def init_decoder(key, cfg: ModelConfig):
    dtype = common.dtype_of(cfg)
    groups = build_layout(cfg)
    k_embed, *gkeys = jax.random.split(key, len(groups) + 1)
    params = {"embed": common.init_embedding(k_embed, cfg, dtype),
              "groups": []}
    for g, gk in zip(groups, gkeys):
        # one layer's init vmapped over the group's keys: the program
        # stays one layer in size however deep the stack is
        params["groups"].append(jax.vmap(
            lambda k, _s=g.spec: _init_layer(k, cfg, _s, dtype))(
                jax.random.split(gk, g.n)))
    return params


def init_decoder_cache(cfg: ModelConfig, batch, buf_len, ctx_len=0):
    dtype = common.dtype_of(cfg)
    groups = build_layout(cfg)
    caches = []
    for g in groups:
        one = _init_layer_cache(cfg, g.spec, batch, buf_len, ctx_len, dtype)
        caches.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (g.n,) + x.shape), one))
    return caches


def decoder_stack(params, cfg: ModelConfig, x, *, mode, caches=None, pos=None,
                  ctx=None):
    """Run all layer groups.  x: [B, S, D] -> ([B, S, D], new_caches)."""
    from repro.distributed.sharding import constrain_seq
    groups = build_layout(cfg)
    caches = caches if caches is not None else [None] * len(groups)
    new_caches = []
    for g, gparams, gcache in zip(groups, params["groups"], caches):
        def body(xc, layer_in, _spec=g.spec):
            lp, lcache = layer_in
            if mode != "decode":
                # sequence-parallel residual stream (no-op off-mesh)
                xc = constrain_seq(xc)
            y, new_c = _layer_forward(lp, cfg, _spec, xc, mode=mode,
                                      cache=lcache, pos=pos, ctx=ctx)
            return y, new_c

        if mode == "train" and cfg.remat:
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.nothing_saveable)

        if mode == "train":
            x, _ = jax.lax.scan(
                lambda xc, lp: (body(xc, (lp, None))[0], None), x, gparams)
            new_caches.append(None)
        elif gcache is None:  # prefill: caches are produced, not consumed
            experts, rest = _held_experts(gparams, g.spec)
            if experts is None:
                x, new_c = jax.lax.scan(
                    lambda xc, lp: body(xc, (lp, None)), x, gparams)
            else:
                x, new_c = jax.lax.scan(
                    lambda xc, li: body(
                        xc, (_view_experts(*li, experts), None)),
                    x, (rest, jnp.arange(g.n)))
            new_caches.append(new_c)
        else:  # decode: the stacked caches ride in the loop's carry
            experts, rest = _held_experts(gparams, g.spec)

            def step(carry, layer_in, _body=body, _e=experts):
                xc, stacks = carry
                stacks = _row_major(stacks)
                lp, i = layer_in
                views = jax.tree.map(lambda s: layers.StackedLayer(s, i),
                                     stacks)
                y, new_c = _body(xc, (_view_experts(lp, i, _e), views))
                return (y, jax.tree.map(lambda s, c: _commit(s, c, i),
                                        stacks, new_c)), None

            (x, new_c), _ = jax.lax.scan(
                step, (x, gcache), (rest, jnp.arange(g.n)))
            new_caches.append(new_c)
    return x, new_caches


def expert_load(caches):
    """The ``moe_load`` stacks of a decoder cache ([n, 2, 2] int32 per MoE
    scan group: per layer, prefill then decode, the routed assignments
    that landed on held experts and the held experts they touched)."""
    return [c["moe_load"] for c in caches
            if isinstance(c, dict) and "moe_load" in c]


def _held_experts(gparams, spec: LayerSpec):
    """(the held experts' stacks, the group's other parameters) for an
    inference scan over a group's layers.  Off a mesh a MoE group's
    ``moe.wi``, ``wg``, ``wo`` ([n, E_held, ...]) leave the scan's
    inputs, and layer i gets ``StackedLayer`` views of them
    (``_view_experts``), which the dropless expert layer reads in place.
    On a mesh, or without experts, (None, gparams)."""
    from repro.distributed.sharding import get_active_mesh
    if spec.kind != "moe" or get_active_mesh() is not None:
        return None, gparams
    moe = dict(gparams["moe"])
    experts = {k: moe.pop(k) for k in ("wi", "wg", "wo")}
    return experts, dict(gparams, moe=moe)


def _view_experts(lp, i, experts):
    """Layer ``i``'s parameters ``lp`` with ``StackedLayer`` views of the
    held experts' stacks (``lp`` itself where ``experts`` is None)."""
    if experts is None:
        return lp
    views = {k: layers.StackedLayer(s, i) for k, s in experts.items()}
    return dict(lp, moe=dict(lp["moe"], **views))


def _row_major(stacks):
    """Pin the carried stacks to the row-major layout they have as the
    decode step's arguments and outputs.  Left free, the TPU compiler may
    give the loop the transposed layout that attention's reads prefer (it
    does at batch 1) and copy each whole stack into it before the loop
    and back after.  On a mesh the stacks are left free: GSPMD has no
    partitioning rule for the constraint and would gather each whole
    stack to every device."""
    from repro.distributed.sharding import get_active_mesh
    if get_active_mesh() is not None:
        return stacks
    return jax.tree.map(
        lambda s: with_layout_constraint(s, Layout(tuple(range(s.ndim)))),
        stacks)


def _commit(stack, new, i):
    """Layer ``i``'s decode output ``new`` for one stacked cache leaf: a
    ``StackedLayer`` is the stack itself, written in place at one
    position or handed back untouched; an array (a recurrent state)
    replaces the layer whole."""
    if isinstance(new, layers.StackedLayer):
        return new.stack
    return jax.lax.dynamic_update_index_in_dim(stack, new, i, 0)


# ----------------------------------------------------------------------------
# encoder stack (seamless-m4t) — bidirectional, scannable, no cache


def init_encoder(key, cfg: ModelConfig):
    dtype = common.dtype_of(cfg)
    n = cfg.encdec.n_enc_layers
    stacked = jax.vmap(lambda k: _init_layer(k, cfg, LayerSpec("dense"),
                                             dtype))(jax.random.split(key, n))
    return {"layers": stacked,
            "final_norm": jnp.zeros((cfg.d_model,), dtype=dtype)}


def encoder_stack(params, cfg: ModelConfig, x, *, remat=False):
    """Bidirectional encoder over stubbed frame embeddings [B, S, D].

    ``attn_sublayer`` is causal, so a non-causal variant is inlined here.
    """

    def body2(xc, lp):
        xin = rms_norm(xc, lp["ln1"], cfg.norm_eps)
        q = jnp.einsum("bsd,dhk->bshk", xin, lp["attn"]["wq"])
        k = jnp.einsum("bsd,dhk->bshk", xin, lp["attn"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", xin, lp["attn"]["wv"])
        positions = jnp.arange(x.shape[1])
        cos, sin = rope_freqs_cached(cfg, positions)
        q = common.apply_rope(q, cos, sin)
        k = common.apply_rope(k, cos, sin)
        out = common.attention(cfg, q, k, v, causal=False)
        xc = xc + jnp.einsum("bshk,hkd->bsd", out, lp["attn"]["wo"])
        xin = rms_norm(xc, lp["ln2"], cfg.norm_eps)
        xc = xc + common.mlp(lp["mlp"], xin, cfg.act)
        return xc, None

    if remat:
        body2 = jax.checkpoint(
            body2, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body2, x, params["layers"])
    return rms_norm(x, params["final_norm"], cfg.norm_eps)


def rope_freqs_cached(cfg, positions):
    return common.rope_freqs(cfg.head_dim, cfg.rope_theta, positions)
