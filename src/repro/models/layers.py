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

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import common
from repro.models.common import (apply_rope, attention, dense_init,
                                 head_rms_norm, rms_norm, rope_freqs)

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


class StackedLayer(NamedTuple):
    """Layer ``i`` of a cache leaf stacked over a scan group's layers
    (``stack`` is [n, B, ...]): what the decoder's decode loop hands a
    sublayer in place of the layer's own buffer."""
    stack: jax.Array
    i: jax.Array

    def read(self):
        return jax.lax.dynamic_index_in_dim(self.stack, self.i, 0,
                                            keepdims=False)


def read_layer(cache):
    """``cache`` with each ``StackedLayer`` view replaced by its layer."""
    return jax.tree.map(StackedLayer.read, cache,
                        is_leaf=lambda c: isinstance(c, StackedLayer))


def _cache_write(buf: StackedLayer, update, idx):
    """Write ``update`` [B, 1, ...] at sequence position ``idx`` of the
    layer ``buf`` views.

    The one position goes into the stack the decode loop carries, at
    (i, :, idx), and attention reads layer ``i`` of the written stack, so
    no layer of the cache is copied.  Returns (the written view, its
    [B, S, ...] layer).
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
    out = StackedLayer(stack, buf.i)
    return out, out.read()


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
        new_k, ck = _cache_write(cache["k"], k, idx)
        new_v, cv = _cache_write(cache["v"], v, idx)
        new_cache = {"k": new_k, "v": new_v}
        buf = ck.shape[1]
        if window is not None:
            # every slot in the ring is within the window once warm; a
            # validity bound covers the cold start.
            k_valid = jnp.minimum(pos + 1, buf)
            out = attention(cfg, q, ck, cv, causal=False, window=None,
                            k_valid=k_valid)
        else:
            out = None
            if SHARDED_DECODE_ATTN:
                from repro.distributed.sharding import \
                    sharded_decode_attention
                out = sharded_decode_attention(q, ck, cv, pos + 1)
            if out is None:
                out = attention(cfg, q, ck, cv, causal=False, window=None,
                                k_valid=pos + 1)
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
        ck, cv = read_layer(cache["ck"]), read_layer(cache["cv"])
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


def mla_sublayer(p, cfg: ModelConfig, x, *, mode, cache, pos,
                 absorb: bool = False):
    """MLA with a compressed latent cache.

    ``absorb=False`` (paper-faithful baseline): decode re-expands k/v from the
    latent via wk_b/wv_b each step.  ``absorb=True`` (§Perf optimization):
    wk_b is absorbed into the query and wv_b into the output projection so
    decode attends directly in the rank-512 latent space.
    """
    m = cfg.mla
    B, S, D = x.shape
    H = cfg.n_heads
    nope, rope_d, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    q = jnp.einsum("bsd,dr->bsr", x, p["wq_a"])
    q = rms_norm(q, p["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", q, p["wq_b"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]

    kv_a = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"])
    ckv = rms_norm(kv_a[..., :m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = kv_a[..., m.kv_lora_rank:]

    if mode == "decode":
        positions = jnp.zeros((S,), jnp.int32) + pos
    else:
        positions = jnp.arange(S)
    cos, sin = rope_freqs(rope_d, cfg.rope_theta, positions)
    q_rope = apply_rope(q_rope, cos, sin)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]

    if mode == "decode":
        new_ckv, ckv_full = _cache_write(cache["ckv"], ckv, pos)
        new_krope, krope_full = _cache_write(cache["krope"], k_rope, pos)
        new_cache = {"ckv": new_ckv, "krope": new_krope}
        k_valid = pos + 1
        causal = False
    else:
        ckv_full, krope_full = ckv, k_rope
        new_cache = ({"ckv": ckv, "krope": k_rope}
                     if mode == "prefill" else None)
        k_valid = None
        causal = True

    if absorb and mode == "decode":
        # fold wk_b into q: q_lat [B,1,H,R]; attend in latent space.
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, p["wk_b"])
        q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)
        k_cat = jnp.concatenate(
            [ckv_full, krope_full], axis=-1)[:, :, None, :]  # MQA: 1 kv head
        out_lat = None
        if SHARDED_DECODE_ATTN:
            from repro.distributed.sharding import sharded_decode_attention
            out_lat = sharded_decode_attention(
                q_cat, k_cat, ckv_full[:, :, None, :], k_valid)
        if out_lat is None:
            out_lat = attention(cfg, q_cat, k_cat, ckv_full[:, :, None, :],
                                causal=False, k_valid=k_valid)
        # out in latent space -> expand through wv_b folded with wo
        y = jnp.einsum("bshr,rhv,hvd->bsd", out_lat, p["wv_b"], p["wo"])
        return y, new_cache

    k_nope = jnp.einsum("bsr,rhk->bshk", ckv_full, p["wk_b"])
    v = jnp.einsum("bsr,rhv->bshv", ckv_full, p["wv_b"])
    k_rope_h = jnp.broadcast_to(krope_full[:, :, None, :],
                                k_nope.shape[:3] + (rope_d,))
    k = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    q_cat = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = attention(cfg, q_cat, k, v, causal=causal, k_valid=k_valid)
    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
    return y, new_cache


# =============================================================================
# MoE FFN — GShard-style capacity dispatch, chunked over tokens
# =============================================================================

MOE_CHUNK = 256  # tokens per dispatch group (baseline; §Perf iterates on this)


def init_moe(key, cfg: ModelConfig, dtype):
    e = cfg.moe
    ff = e.d_ff_expert or cfg.d_ff
    ks = jax.random.split(key, 5)
    D = cfg.d_model
    p = {
        "router": dense_init(ks[0], (D, e.n_experts), in_axis=0,
                             dtype=jnp.float32),
        "wi": dense_init(ks[1], (e.n_experts, D, ff), in_axis=1, dtype=dtype),
        "wg": dense_init(ks[2], (e.n_experts, D, ff), in_axis=1, dtype=dtype),
        "wo": dense_init(ks[3], (e.n_experts, ff, D), in_axis=-1, dtype=dtype),
    }
    if e.n_shared:
        p["shared"] = common.init_mlp(ks[4], D, e.n_shared * ff, dtype)
    return p


def _route(p, cfg: ModelConfig, xf):
    """xf: [T, D] -> (gates [T, E] f32 with zeros off top-k, mask [T, E])."""
    e = cfg.moe
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, e.top_k)
    mask = jax.nn.one_hot(top_idx, e.n_experts, dtype=jnp.float32).sum(axis=1)
    gates = probs * mask
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, mask


def _route_grouped(p, cfg: ModelConfig, xg):
    """xg: [B, G, T, D] -> (gates, mask) [B, G, T, E] f32."""
    e = cfg.moe
    logits = jnp.einsum("bgtd,de->bgte", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, e.top_k)
    mask = jax.nn.one_hot(top_idx, e.n_experts, dtype=jnp.float32).sum(axis=-2)
    gates = probs * mask
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, mask


def moe_ffn(p, cfg: ModelConfig, x):
    """GShard grouped capacity dispatch (Mesh-TF/GSPMD formulation).

    Tokens keep their [B, S] layout: the sequence is split into groups of
    MOE_CHUNK tokens and each group dispatches into per-expert capacity
    buffers via dense one-hot einsums.  The group structure (instead of a
    flat token lax.map) is what keeps every tensor shardable: batch stays
    on the DP axes and the expert axis is constrained onto 'model'.
    """
    from repro.distributed.sharding import (constrain_moe_expert,
                                            constrain_moe_groups)
    e = cfg.moe
    B, S, D = x.shape
    g = min(MOE_CHUNK, S)
    if S % g:
        g = S
    G = S // g
    capacity = max(e.top_k, int(g / e.n_experts * e.top_k
                                * e.capacity_factor))
    xg = constrain_moe_groups(x.reshape(B, G, g, D))
    gates, mask = _route_grouped(p, cfg, xg)
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
    out = constrain_moe_groups(out.reshape(B, G, g, D)).reshape(B, S, D)
    if e.n_shared:
        out = out + common.mlp(p["shared"], x, cfg.act)
    return out


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
