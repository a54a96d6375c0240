"""Layer types for every assigned architecture family.

Uniform sublayer interface so the generic decoder can scan stacked layers:

    init_<kind>_layer(key, cfg) -> params (single layer)
    <kind>_layer(params, cfg, x, *, mode, cache, pos, ctx) -> (y, new_cache)

``mode``  : "train" | "prefill" | "decode"
``cache`` : per-layer cache pytree (None in train mode); in decode its
            leaves are ``StackedLayer`` views of the decoder's stacked
            cache (see ``_cache_write``)
``pos``   : scalar int32 — absolute position of the incoming token (decode)
``ctx``   : encoder/vision context [B, S_ctx, D] for cross-attention layers
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common
from repro.models.common import (StackedLayer, apply_rope, attention,
                                 dense_init, head_rms_norm, rms_norm,
                                 rope_freqs)

# When True, decode-cache writes use a one-hot masked update instead of
# dynamic_update_slice.  DUS at a dynamic index on a sequence-sharded cache
# makes GSPMD gather the full cache per layer ("involuntary full remat");
# the one-hot formulation is purely elementwise and stays shard-local (the
# written layer goes back into its stack along the unsharded layer axis).
# (§Perf optimization — the paper-faithful baseline uses DUS.)
ONEHOT_CACHE_UPDATE = False

# When True, full-cache decode attention runs as an explicit shard_map
# flash-decode (local online-softmax stats merged with pmax/psum) instead
# of letting GSPMD all-gather the sequence-sharded cache (§Perf).
SHARDED_DECODE_ATTN = False


def read_layer(cache):
    """``cache`` with each ``StackedLayer`` view replaced by its layer."""
    return jax.tree.map(StackedLayer.read, cache,
                        is_leaf=lambda c: isinstance(c, StackedLayer))


def _cache_write(buf: StackedLayer, update, idx):
    """Write ``update`` [B, 1, ...] at sequence position ``idx`` of the
    layer ``buf`` views.

    The one position goes into the stack the decode loop carries, at
    (i, :, idx), so no layer of the cache is copied.  Returns the written
    view, which decode attention reads where it lies.
    """
    if ONEHOT_CACHE_UPDATE:
        layer = buf.read()
        S = layer.shape[1]
        onehot = (jnp.arange(S, dtype=jnp.int32) == idx).astype(layer.dtype)
        onehot = onehot.reshape((1, S) + (1,) * (layer.ndim - 2))
        layer = layer * (1 - onehot) + update.astype(layer.dtype) * onehot
        stack = jax.lax.dynamic_update_index_in_dim(buf.stack, layer, buf.i,
                                                    0)
    else:
        zero = jnp.zeros((), jnp.int32)
        start = (buf.i, zero, idx) + (zero,) * (update.ndim - 2)
        stack = jax.lax.dynamic_update_slice(buf.stack, update[None], start)
    return StackedLayer(stack, buf.i)


def init_moe_load():
    """A MoE layer's ``held_load`` counts as its cache carries them: row 0
    those of the prefill, row 1 those summed over the decode steps."""
    return jnp.zeros((2, 2), jnp.int32)


def count_decode_load(buf: StackedLayer, load):
    """Add one decode step's ``held_load`` to row 1 of the layer ``buf``
    views, in the stack the decode loop carries."""
    layer = buf.read().at[1].add(load)
    return StackedLayer(
        jax.lax.dynamic_update_index_in_dim(buf.stack, layer, buf.i, 0),
        buf.i)


# =============================================================================
# GQA self-attention sublayer (dense / moe / vlm-self / hymba-attn-branch)
# =============================================================================


def init_attention(key, cfg: ModelConfig, dtype):
    ks = jax.random.split(key, 4)
    H, K, hd, D = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_model
    p = {
        "wq": dense_init(ks[0], (D, H, hd), in_axis=0, dtype=dtype),
        "wk": dense_init(ks[1], (D, K, hd), in_axis=0, dtype=dtype),
        "wv": dense_init(ks[2], (D, K, hd), in_axis=0, dtype=dtype),
        "wo": dense_init(ks[3], (H, hd, D), in_axis=-1, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype=dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype=dtype)
    return p


def init_attn_cache(cfg: ModelConfig, batch, buf_len, dtype):
    K, hd = cfg.n_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((batch, buf_len, K, hd), dtype=dtype),
        "v": jnp.zeros((batch, buf_len, K, hd), dtype=dtype),
    }


def attn_sublayer(p, cfg: ModelConfig, x, *, mode, cache, pos, window):
    """x: [B, S, D].  Ring-buffer cache when ``window`` is set."""
    B, S, D = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = head_rms_norm(k, p["k_norm"], cfg.norm_eps)

    if mode == "decode":
        positions = jnp.full((S,), 0, jnp.int32) + pos  # S == 1
    else:
        positions = jnp.arange(S)
    cos, sin = rope_freqs(cfg.head_dim, cfg.rope_theta, positions)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)

    if mode == "train":
        out = attention(cfg, q, k, v, causal=True, window=window)
        new_cache = None
    elif mode == "prefill":
        out = attention(cfg, q, k, v, causal=True, window=window)
        if window is not None:
            # ring buffer holding the last `window` tokens (S % window == 0
            # guaranteed by the shape cells; see DESIGN.md)
            new_cache = {"k": k[:, -window:], "v": v[:, -window:]}
        else:
            new_cache = {"k": k, "v": v}
    else:  # decode: write one token, attend over the cache
        if window is not None:
            idx = jax.lax.rem(pos, jnp.int32(window))
        else:
            idx = pos
        new_k = _cache_write(cache["k"], k, idx)
        new_v = _cache_write(cache["v"], v, idx)
        new_cache = {"k": new_k, "v": new_v}
        buf = new_k.shape[1]
        if window is not None:
            # every slot in the ring is within the window once warm; a
            # validity bound covers the cold start.
            k_valid = jnp.minimum(pos + 1, buf)
            out = attention(cfg, q, new_k, new_v, causal=False, window=None,
                            k_valid=k_valid)
        else:
            out = None
            if SHARDED_DECODE_ATTN:
                from repro.distributed.sharding import \
                    sharded_decode_attention
                out = sharded_decode_attention(q, new_k.read(),
                                               new_v.read(), pos + 1)
            if out is None:
                out = attention(cfg, q, new_k, new_v, causal=False,
                                window=None, k_valid=pos + 1)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


# =============================================================================
# Cross-attention sublayer (VLM image layers, enc-dec decoder)
# =============================================================================


def init_cross_attention(key, cfg: ModelConfig, dtype, gated: bool):
    p = init_attention(key, cfg, dtype)
    if gated:  # llama-3.2-vision style tanh gates
        p["gate_attn"] = jnp.zeros((), dtype=dtype)
        p["gate_ffn"] = jnp.zeros((), dtype=dtype)
    return p


def cross_sublayer(p, cfg: ModelConfig, x, *, mode, cache, ctx):
    """Cross-attn: queries from x, keys/values from ctx (cached after first
    computation — ctx is static across decode steps)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_norm"], cfg.norm_eps)
    if mode == "decode" and cache is not None:
        ck, cv = cache["ck"], cache["cv"]   # views, read where they lie
        new_cache = cache  # read only: handed back as it came
    else:
        ck = jnp.einsum("bsd,dhk->bshk", ctx, p["wk"])
        cv = jnp.einsum("bsd,dhk->bshk", ctx, p["wv"])
        if cfg.qk_norm:
            ck = head_rms_norm(ck, p["k_norm"], cfg.norm_eps)
        new_cache = {"ck": ck, "cv": cv} if mode != "train" else None
    out = attention(cfg, q, ck, cv, causal=False, window=None)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, new_cache


# =============================================================================
# MLA — multi-head latent attention (deepseek-v2)
# =============================================================================


def init_mla(key, cfg: ModelConfig, dtype):
    m = cfg.mla
    ks = jax.random.split(key, 6)
    D, H = cfg.d_model, cfg.n_heads
    qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": dense_init(ks[0], (D, m.q_lora_rank), in_axis=0, dtype=dtype),
        "q_norm": jnp.zeros((m.q_lora_rank,), dtype=dtype),
        "wq_b": dense_init(ks[1], (m.q_lora_rank, H, qk_hd), in_axis=0,
                           dtype=dtype),
        "wkv_a": dense_init(ks[2], (D, m.kv_lora_rank + m.qk_rope_head_dim),
                            in_axis=0, dtype=dtype),
        "kv_norm": jnp.zeros((m.kv_lora_rank,), dtype=dtype),
        "wk_b": dense_init(ks[3], (m.kv_lora_rank, H, m.qk_nope_head_dim),
                           in_axis=0, dtype=dtype),
        "wv_b": dense_init(ks[4], (m.kv_lora_rank, H, m.v_head_dim),
                           in_axis=0, dtype=dtype),
        "wo": dense_init(ks[5], (H, m.v_head_dim, D), in_axis=-1, dtype=dtype),
    }


def init_mla_cache(cfg: ModelConfig, batch, buf_len, dtype):
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, buf_len, m.kv_lora_rank), dtype=dtype),
        "krope": jnp.zeros((batch, buf_len, m.qk_rope_head_dim), dtype=dtype),
    }


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """1/sqrt(qk_nope + qk_rope), times YaRN's mscale^2 where the rope is
    scaled (DeepSeek-V2's ``softmax_scale``)."""
    m = cfg.mla
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    y = cfg.rope_scaling
    if y is not None and y.mscale_all_dim:
        scale *= common.yarn_mscale(y.factor, y.mscale_all_dim) ** 2
    return scale


def mla_sublayer(p, cfg: ModelConfig, x, *, mode, cache, pos):
    """MLA with a compressed latent cache (DeepSeek-V2).

    The cache holds, per position, the normed latent ``ckv``
    (kv_lora_rank) and the roped shared key ``krope`` (qk_rope_head_dim).
    Train and prefill re-expand k and v per head from the latent through
    ``wk_b``/``wv_b``.  Decode attends in the latent space: ``wk_b`` is
    folded into the query and ``wv_b`` into the output, so the query
    heads read the one shared latent head of the cache and no per-head k
    or v exists.  Both use the published softmax scale
    (``mla_softmax_scale``) and YaRN rope on the rope dims.
    """
    m = cfg.mla
    B, S, D = x.shape
    nope, rope_d = m.qk_nope_head_dim, m.qk_rope_head_dim

    with jax.named_scope("mla.project"):
        q = jnp.einsum("bsd,dr->bsr", x, p["wq_a"])
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", q, p["wq_b"])
        q_nope, q_rope = q[..., :nope], q[..., nope:]

        kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
        ckv = rms_norm(kv_a[..., :m.kv_lora_rank], p["kv_norm"],
                       cfg.norm_eps)
        k_rope = kv_a[..., m.kv_lora_rank:]

        if mode == "decode":
            positions = jnp.zeros((S,), jnp.int32) + pos
        else:
            positions = jnp.arange(S)
        cos, sin = rope_freqs(rope_d, cfg.rope_theta, positions,
                              cfg.rope_scaling)
        q_rope = apply_rope(q_rope, cos, sin)
        k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    scale = mla_softmax_scale(cfg)

    if mode == "decode":
        new_ckv = _cache_write(cache["ckv"], ckv, pos)
        new_krope = _cache_write(cache["krope"], k_rope, pos)
        new_cache = {"ckv": new_ckv, "krope": new_krope}
        with jax.named_scope("mla.project"):
            q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wk_b"])
            q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)
        with jax.named_scope("mla.latent_attend"):
            # one latent head shared by every query head (MQA); the
            # values are the latent itself, read where the stack holds it
            ckv_full = new_ckv.read()
            k_cat = jnp.concatenate([ckv_full, new_krope.read()],
                                    axis=-1)[:, :, None, :]
            v_lat = StackedLayer(new_ckv.stack[..., None, :], new_ckv.i)
            out_lat = None
            if SHARDED_DECODE_ATTN:     # it scales by 1/sqrt(head dim)
                from repro.distributed.sharding import \
                    sharded_decode_attention
                out_lat = sharded_decode_attention(
                    q_cat * (scale * q_cat.shape[-1] ** 0.5), k_cat,
                    ckv_full[:, :, None, :], pos + 1)
            if out_lat is None:
                out_lat = attention(cfg, q_cat, k_cat, v_lat, causal=False,
                                    k_valid=pos + 1, scale=scale)
        with jax.named_scope("mla.project"):
            out = jnp.einsum("bshr,rhv->bshv", out_lat, p["wv_b"])
            y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
        return y, new_cache

    new_cache = {"ckv": ckv, "krope": k_rope} if mode == "prefill" else None
    with jax.named_scope("mla.project"):
        k_nope = jnp.einsum("bsr,rhk->bshk", ckv, p["wk_b"])
        v = jnp.einsum("bsr,rhv->bshv", ckv, p["wv_b"])
        k_rope_h = jnp.broadcast_to(k_rope[:, :, None, :],
                                    k_nope.shape[:3] + (rope_d,))
        k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
        q_cat = jnp.concatenate([q_nope, q_rope], axis=-1)
    with jax.named_scope("mla.attend"):
        out = attention(cfg, q_cat, k, v, causal=True, scale=scale)
    with jax.named_scope("mla.project"):
        y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
    return y, new_cache


# =============================================================================
# MoE FFN: group-limited routing; dropless over the held experts on one
# device, GShard capacity dispatch on a mesh
# =============================================================================

MOE_CHUNK = 256  # tokens per dispatch group (capacity path)
MOE_ROWS = 8192  # routed rows one pass of the held experts computes


def init_moe(key, cfg: ModelConfig, dtype):
    """Router over all ``n_experts``; weights of the held experts only.
    Expert e's weights are drawn from its own key (the e-th of
    ``n_experts``), so a share's weights are the uncut layer's rows."""
    e = cfg.moe
    ff = e.d_ff_expert or cfg.d_ff
    first, held = e.held
    ks = jax.random.split(key, 5)
    D = cfg.d_model

    def experts(k, shape, in_axis):
        keys = jax.random.split(k, e.n_experts)[first:first + held]
        return jax.vmap(lambda kk: dense_init(kk, shape, in_axis=in_axis,
                                              dtype=dtype))(keys)

    p = {
        "router": dense_init(ks[0], (D, e.n_experts), in_axis=0,
                             dtype=jnp.float32),
        "wi": experts(ks[1], (D, ff), 0),
        "wg": experts(ks[2], (D, ff), 0),
        "wo": experts(ks[3], (ff, D), -2),
    }
    if e.n_shared:
        p["shared"] = common.init_mlp(ks[4], D, e.n_shared * ff, dtype)
    return p


def route(p, cfg: ModelConfig, x):
    """x: [..., D] -> (weights [..., top_k] f32, experts [..., top_k]).

    Softmax over every router logit in f32.  With ``n_group`` > 1 a
    group's score is its best expert's, and only the experts of the
    ``topk_group`` best groups can be chosen (DeepSeek-V2's
    ``group_limited_greedy``).  The chosen scores are renormalised
    (``norm_topk_prob``) or else scaled by ``routed_scaling_factor``."""
    e = cfg.moe
    # HIGHEST: at the default precision the TPU rounds both f32 operands
    # to bf16, which flips choices between near-tied experts
    logits = jnp.einsum("...d,de->...e", x.astype(jnp.float32), p["router"],
                        precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)
    if e.n_group > 1:
        groups = scores.reshape(scores.shape[:-1] + (e.n_group, -1))
        _, best = jax.lax.top_k(groups.max(axis=-1), e.topk_group)
        kept = jax.nn.one_hot(best, e.n_group, dtype=jnp.bool_).any(axis=-2)
        scores = jnp.where(kept[..., None], groups, 0.0).reshape(
            scores.shape)
    w, idx = jax.lax.top_k(scores, e.top_k)
    if e.norm_topk_prob:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    else:
        w = w * e.routed_scaling_factor
    return w, idx


def _held_counts(cfg: ModelConfig, idx):
    """For expert choices ``idx``: the held expert of each (its index in
    the share, or ``held`` where it is not held), flat, and the count of
    choices of each held expert."""
    first, held = cfg.moe.held
    local = idx.reshape(-1) - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    return key, jnp.zeros((held,), jnp.int32).at[key].add(1, mode="drop")


def held_load(cfg: ModelConfig, idx):
    """[routed assignments that land on held experts, held experts with
    at least one of them] (int32) for expert choices ``idx``."""
    _, counts = _held_counts(cfg, idx)
    return jnp.stack([counts.sum(), (counts > 0).sum().astype(jnp.int32)])


def moe_ffn(p, cfg: ModelConfig, x):
    """The routed experts' part of the output (of the held experts only)
    plus the shared experts; returns (out [B, S, D], ``held_load``).

    Off a mesh (the serving engine on one device) every routed assignment
    to a held expert is computed, none dropped (``_experts_dropless``).
    On a mesh (the dry-run) the GShard capacity dispatch shards the
    experts over 'model' and holds all of them (``_experts_capacity``):
    the dropless layer compiles there too, but GSPMD does not partition
    its grouped products, and a training step's temporaries grow several
    times over.
    """
    from repro.distributed.sharding import get_active_mesh
    e = cfg.moe
    B, S, D = x.shape
    with jax.named_scope("moe.route"):
        w, idx = route(p, cfg, x)
        load = held_load(cfg, idx)
    with jax.named_scope("moe.experts"):
        if get_active_mesh() is None:
            out = _experts_dropless(p, cfg, x.reshape(B * S, D),
                                    w.reshape(B * S, -1),
                                    idx.reshape(B * S, -1)).reshape(B, S, D)
        else:
            out = _experts_capacity(p, cfg, x, w, idx)
    if e.n_shared:
        with jax.named_scope("moe.shared"):
            out = out + common.mlp(p["shared"], x, cfg.act)
    return out, load


def _experts_dropless(p, cfg: ModelConfig, xf, w, idx):
    """xf: [T, D]; w, idx: [T, k].  The held experts' weighted outputs
    summed per token, [T, D], without drops.

    The T * k assignments are sorted by expert (those to experts not held
    last) and run through the held experts as one grouped matrix product
    (``lax.ragged_dot``) per pass of up to ``MOE_ROWS`` rows; the passes
    stop after the last held assignment, so a held expert computes only
    the tokens routed to it and the absent experts cost nothing.  The
    weights are arrays, or ``StackedLayer`` views of the decoder's
    stacked experts (``_grouped_weights``)."""
    T, k = idx.shape
    (wi, wg, wo), place = _grouped_weights(p)
    key, counts = _held_counts(cfg, idx)
    order = jnp.argsort(key, stable=True)
    tok = order // k
    wt = jnp.where(key < counts.shape[0], w.reshape(-1), 0.0)[order]
    ends = jnp.cumsum(counts)
    starts = ends - counts
    n = ends[-1]
    M = T * k
    C = min(M, MOE_ROWS)
    passes = -(-M // C)
    tok = jnp.pad(tok, (0, passes * C - M))
    wt = jnp.pad(wt, (0, passes * C - M))

    def one_pass(c, out):
        lo = c * C
        t = jax.lax.dynamic_slice_in_dim(tok, lo, C)
        wc = jax.lax.dynamic_slice_in_dim(wt, lo, C)
        sizes = place(jnp.clip(ends, lo, lo + C)
                      - jnp.clip(starts, lo, lo + C))
        xs = xf[t]
        a = jax.lax.ragged_dot(xs, wi, sizes)
        g = jax.lax.ragged_dot(xs, wg, sizes)
        act = jax.nn.gelu(g) if cfg.act == "gelu" else jax.nn.silu(g)
        y = jax.lax.ragged_dot(a * act, wo, sizes)
        valid = lo + jnp.arange(C) < n
        y = jnp.where(valid[:, None], y.astype(jnp.float32) * wc[:, None],
                      0.0)
        return out.at[t].add(y)

    out = jnp.zeros(xf.shape, jnp.float32)
    if passes == 1:
        out = one_pass(0, out)
    else:
        # a static count of passes keeps the layer differentiable, as
        # training needs; a pass past the last held assignment is skipped
        skip = lambda c, o: o
        out, _ = jax.lax.scan(
            lambda o, c: (jax.lax.cond(c * C < n, one_pass, skip, c, o),
                          None), out, jnp.arange(passes))
    return out.astype(xf.dtype)


def _grouped_weights(p):
    """The held experts' ``wi``, ``wg``, ``wo`` as ``lax.ragged_dot``'s
    groups, and what turns a pass's sizes of the held experts into its
    sizes of those groups.

    ``StackedLayer`` views of layer i of [n, E, ...] stacks are read
    where the stacks lie: each stack is n * E groups (for a row-major
    stack the reshape is a bitcast) and the held experts' sizes go at
    i * E, the other groups empty.  The kernel is a custom call, which
    cannot read a slice of its operand in place, so handing it layer i
    alone would copy the layer's experts."""
    ws = [p[k] for k in ("wi", "wg", "wo")]
    if not isinstance(ws[0], StackedLayer):
        return ws, lambda sizes: sizes
    n, E = ws[0].stack.shape[:2]
    i = ws[0].i
    flat = [w.stack.reshape((n * E,) + w.stack.shape[2:]) for w in ws]
    return flat, lambda sizes: jax.lax.dynamic_update_slice(
        jnp.zeros((n * E,), sizes.dtype), sizes, (i * E,))


def _experts_capacity(p, cfg: ModelConfig, x, w, idx):
    """GShard grouped capacity dispatch (Mesh-TF/GSPMD formulation).

    Tokens keep their [B, S] layout: the sequence is split into groups of
    MOE_CHUNK tokens and each group dispatches into per-expert capacity
    buffers via dense one-hot einsums.  The group structure (instead of a
    flat token lax.map) is what keeps every tensor shardable: batch stays
    on the DP axes and the expert axis is constrained onto 'model'.  A
    token past its expert's capacity is dropped.
    """
    from repro.distributed.sharding import (constrain_moe_expert,
                                            constrain_moe_groups)
    e = cfg.moe
    if e.held[1] != e.n_experts:
        raise ValueError("the capacity dispatch holds every expert")
    B, S, D = x.shape
    g = min(MOE_CHUNK, S)
    if S % g:
        g = S
    G = S // g
    capacity = max(e.top_k, int(g / e.n_experts * e.top_k
                                * e.capacity_factor))
    xg = constrain_moe_groups(x.reshape(B, G, g, D))
    onehot = jax.nn.one_hot(idx.reshape(B, G, g, -1), e.n_experts,
                            dtype=jnp.float32)
    mask = onehot.sum(axis=-2)                            # [B,G,T,E]
    gates = (onehot * w.reshape(B, G, g, -1, 1)).sum(axis=-2)
    # position of each token within its expert's capacity buffer (per group)
    pos_in_exp = jnp.cumsum(mask, axis=2) - 1.0
    keep = mask * (pos_in_exp < capacity)
    dispatch = keep[..., None] * jax.nn.one_hot(
        pos_in_exp.astype(jnp.int32), capacity,
        dtype=jnp.float32)                                # [B,G,T,E,C]
    combine = dispatch * gates[..., None]
    dt = x.dtype
    # dispatch: tokens leave their (group-sharded) layout for the
    # expert-sharded layout -> all-to-all over 'model' (classic MoE EP)
    exp_in = constrain_moe_expert(
        jnp.einsum("bgtec,bgtd->bgecd", dispatch.astype(dt), xg))
    a = jnp.einsum("bgecd,edf->bgecf", exp_in, p["wi"])
    h = jnp.einsum("bgecd,edf->bgecf", exp_in, p["wg"])
    act = jax.nn.gelu(h) if cfg.act == "gelu" else jax.nn.silu(h)
    exp_out = constrain_moe_expert(
        jnp.einsum("bgecf,efd->bgecd", a * act, p["wo"]))
    out = jnp.einsum("bgtec,bgecd->bgtd", combine.astype(dt), exp_out)
    return constrain_moe_groups(out.reshape(B, G, g, D)).reshape(B, S, D)


# =============================================================================
# RWKV6 (Finch) — time-mix with data-dependent decay + channel-mix
# =============================================================================


def init_rwkv_layer(key, cfg: ModelConfig, dtype):
    s = cfg.ssm
    D, R = cfg.d_model, s.lora_rank
    H = D // s.rwkv_head_dim
    ks = jax.random.split(key, 16)
    p = {"tm": {}, "cm": {}}
    tm = p["tm"]
    for i, nm in enumerate(["x", "r", "k", "v", "w", "g"]):
        tm[f"mu_{nm}"] = jnp.zeros((D,), dtype=dtype)
    for i, nm in enumerate(["r", "k", "v", "w", "g"]):
        tm[f"lora_{nm}_a"] = dense_init(ks[i], (D, R), in_axis=0, dtype=dtype)
        tm[f"lora_{nm}_b"] = (jnp.zeros((R, D), dtype=dtype))
    tm["w0"] = jnp.full((D,), -1.0, dtype=dtype)  # decay base
    tm["u"] = dense_init(ks[10], (D,), dtype=dtype)  # per-channel bonus
    tm["wr"] = dense_init(ks[11], (D, D), in_axis=0, dtype=dtype)
    tm["wk"] = dense_init(ks[12], (D, D), in_axis=0, dtype=dtype)
    tm["wv"] = dense_init(ks[13], (D, D), in_axis=0, dtype=dtype)
    tm["wg"] = dense_init(ks[14], (D, D), in_axis=0, dtype=dtype)
    tm["wo"] = dense_init(ks[15], (D, D), in_axis=0, dtype=dtype)
    tm["ln_x"] = jnp.zeros((D,), dtype=dtype)
    k2 = jax.random.split(ks[0], 4)
    cm = p["cm"]
    cm["mu_k"] = jnp.zeros((D,), dtype=dtype)
    cm["mu_r"] = jnp.zeros((D,), dtype=dtype)
    cm["wk"] = dense_init(k2[0], (D, cfg.d_ff), in_axis=0, dtype=dtype)
    cm["wv"] = dense_init(k2[1], (cfg.d_ff, D), in_axis=0, dtype=dtype)
    cm["wr"] = dense_init(k2[2], (D, D), in_axis=0, dtype=dtype)
    return p


def init_rwkv_cache(cfg: ModelConfig, batch, dtype):
    D = cfg.d_model
    hd = cfg.ssm.rwkv_head_dim
    H = D // hd
    return {
        "state": jnp.zeros((batch, H, hd, hd), jnp.float32),
        "tm_shift": jnp.zeros((batch, D), dtype=dtype),
        "cm_shift": jnp.zeros((batch, D), dtype=dtype),
    }


def _rwkv_mix(tm, x, x_prev):
    """ddlerp token mixing. x, x_prev: [B, S, D] (x_prev = token-shifted x)."""
    dx = x_prev - x
    xx = x + dx * tm["mu_x"]
    outs = {}
    for nm in ["r", "k", "v", "w", "g"]:
        lo = jnp.tanh(jnp.einsum("bsd,dr->bsr", xx, tm[f"lora_{nm}_a"]))
        lo = jnp.einsum("bsr,rd->bsd", lo, tm[f"lora_{nm}_b"])
        outs[nm] = x + dx * (tm[f"mu_{nm}"] + lo)
    return outs


def rwkv_time_mix(p, cfg: ModelConfig, x, *, mode, cache):
    """RWKV6 WKV recurrence.  Sequential lax.scan over time (the Pallas
    ``rwkv_scan`` kernel implements the chunked TPU version of this math)."""
    tm = p["tm"]
    B, S, D = x.shape
    hd = cfg.ssm.rwkv_head_dim
    H = D // hd

    if mode == "decode":
        x_prev = cache["tm_shift"][:, None, :]
    else:
        x_prev = jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)
    m = _rwkv_mix(tm, x, x_prev)

    r = jnp.einsum("bsd,de->bse", m["r"], tm["wr"]).reshape(B, S, H, hd)
    k = jnp.einsum("bsd,de->bse", m["k"], tm["wk"]).reshape(B, S, H, hd)
    v = jnp.einsum("bsd,de->bse", m["v"], tm["wv"]).reshape(B, S, H, hd)
    g = jax.nn.silu(jnp.einsum("bsd,de->bse", m["g"], tm["wg"]))
    # data-dependent decay w_t in (0, 1), computed in f32 for stability
    w = jnp.exp(-jnp.exp((tm["w0"] + m["w"]).astype(jnp.float32)))
    w = w.reshape(B, S, H, hd)
    u = tm["u"].reshape(H, hd).astype(jnp.float32)

    rf = r.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)

    state0 = (cache["state"] if mode == "decode"
              else jnp.zeros((B, H, hd, hd), jnp.float32))

    def step(state, inp):
        rt, kt, vt, wt = inp  # [B, H, hd]
        kv = kt[..., :, None] * vt[..., None, :]        # [B, H, hdk, hdv]
        out = jnp.einsum("bhk,bhkv->bhv", rt,
                         state + u[None, :, :, None] * kv)
        state = wt[..., :, None] * state + kv
        return state, out

    xs = (rf.transpose(1, 0, 2, 3), kf.transpose(1, 0, 2, 3),
          vf.transpose(1, 0, 2, 3), w.transpose(1, 0, 2, 3))
    state_end, outs = common.chunked_time_scan(step, state0, xs, S)
    out = outs.transpose(1, 0, 2, 3).reshape(B, S, D)

    # per-head group-norm, then gate and output-project
    out = out.reshape(B, S, H, hd)
    mu = out.mean(-1, keepdims=True)
    var = out.var(-1, keepdims=True)
    out = (out - mu) * jax.lax.rsqrt(var + 64e-5)
    out = out.reshape(B, S, D) * (1.0 + p["tm"]["ln_x"].astype(jnp.float32))
    out = (out.astype(x.dtype) * g.astype(x.dtype))
    y = jnp.einsum("bsd,de->bse", out, tm["wo"])

    new_cache = None
    if mode != "train":
        new_cache = {"state": state_end, "tm_shift": x[:, -1, :]}
    return y, new_cache


def rwkv_channel_mix(p, cfg: ModelConfig, x, *, mode, cache):
    cm = p["cm"]
    if mode == "decode":
        x_prev = cache[:, None, :]
    else:
        x_prev = jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)
    dx = x_prev - x
    xk = x + dx * cm["mu_k"]
    xr = x + dx * cm["mu_r"]
    k = jnp.einsum("bsd,df->bsf", xk, cm["wk"])
    k = jnp.square(jax.nn.relu(k))
    v = jnp.einsum("bsf,fd->bsd", k, cm["wv"])
    r = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", xr, cm["wr"]))
    y = r * v
    new_shift = x[:, -1, :] if mode != "train" else None
    return y, new_shift


# =============================================================================
# Mamba selective-SSM branch (hymba hybrid heads)
# =============================================================================


def init_mamba(key, cfg: ModelConfig, dtype):
    s = cfg.ssm
    D = cfg.d_model
    di = s.d_inner_mult * D
    dt_rank = s.dt_rank or max(1, -(-D // 16))
    ks = jax.random.split(key, 7)
    return {
        "in_proj": dense_init(ks[0], (D, 2 * di), in_axis=0, dtype=dtype),
        "conv_w": dense_init(ks[1], (s.d_conv, di), in_axis=0, dtype=dtype),
        "x_proj": dense_init(ks[2], (di, dt_rank + 2 * s.state_dim),
                             in_axis=0, dtype=dtype),
        "dt_proj": dense_init(ks[3], (dt_rank, di), in_axis=0, dtype=dtype),
        "dt_bias": jnp.full((di,), -4.0, dtype=dtype),
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, s.state_dim + 1, dtype=jnp.float32), (di, s.state_dim))
        ).astype(jnp.float32),
        "Dskip": jnp.ones((di,), dtype=dtype),
        "out_proj": dense_init(ks[4], (di, D), in_axis=0, dtype=dtype),
    }


def init_mamba_cache(cfg: ModelConfig, batch, dtype):
    s = cfg.ssm
    di = s.d_inner_mult * cfg.d_model
    return {
        "conv": jnp.zeros((batch, s.d_conv - 1, di), dtype=dtype),
        "ssm": jnp.zeros((batch, di, s.state_dim), jnp.float32),
    }


def mamba_branch(p, cfg: ModelConfig, x, *, mode, cache):
    """Selective scan.  x: [B, S, D] -> [B, S, D]."""
    s = cfg.ssm
    B, S, D = x.shape
    di = s.d_inner_mult * D
    N = s.state_dim

    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    xi, z = xz[..., :di], xz[..., di:]

    # causal depthwise conv, width d_conv
    if mode == "decode":
        hist = jnp.concatenate([cache["conv"], xi], axis=1)  # [B, d_conv, di]
        conv_out = jnp.einsum("bkc,kc->bc", hist, p["conv_w"])[:, None, :]
        new_conv = hist[:, 1:, :]
    else:
        pad = jnp.zeros((B, s.d_conv - 1, di), xi.dtype)
        hist = jnp.concatenate([pad, xi], axis=1)
        conv_out = sum(
            hist[:, i:i + S, :] * p["conv_w"][i][None, None, :]
            for i in range(s.d_conv))
        new_conv = hist[:, S:, :] if mode == "prefill" else None
    xc = jax.nn.silu(conv_out)

    proj = jnp.einsum("bsc,ce->bse", xc, p["x_proj"])
    dt_rank = p["dt_proj"].shape[0]
    dt = jax.nn.softplus(
        jnp.einsum("bsr,rc->bsc", proj[..., :dt_rank], p["dt_proj"])
        + p["dt_bias"]).astype(jnp.float32)                  # [B,S,di]
    Bt = proj[..., dt_rank:dt_rank + N].astype(jnp.float32)  # [B,S,N]
    Ct = proj[..., dt_rank + N:].astype(jnp.float32)         # [B,S,N]
    A = -jnp.exp(p["A_log"])                                 # [di,N]
    xcf = xc.astype(jnp.float32)

    h0 = (cache["ssm"] if mode == "decode"
          else jnp.zeros((B, di, N), jnp.float32))

    def step(h, inp):
        dt_t, B_t, C_t, x_t = inp  # [B,di],[B,N],[B,N],[B,di]
        dA = jnp.exp(dt_t[..., None] * A[None])              # [B,di,N]
        dBx = dt_t[..., None] * B_t[:, None, :] * x_t[..., None]
        h = dA * h + dBx
        y = jnp.einsum("bcn,bn->bc", h, C_t)
        return h, y

    xs = (dt.transpose(1, 0, 2), Bt.transpose(1, 0, 2),
          Ct.transpose(1, 0, 2), xcf.transpose(1, 0, 2))
    h_end, ys = common.chunked_time_scan(step, h0, xs, S)
    y = ys.transpose(1, 0, 2)                                # [B,S,di]
    y = y + xcf * p["Dskip"].astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    out = jnp.einsum("bsc,cd->bsd", y, p["out_proj"])

    new_cache = None
    if mode != "train":
        new_cache = {"conv": new_conv if new_conv is not None
                     else cache["conv"], "ssm": h_end}
    return out, new_cache
