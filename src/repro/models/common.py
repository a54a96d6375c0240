"""Shared model primitives: norms, RoPE, attention (all variants), MLPs.

All attention here is the pure-JAX (XLA) path, the one every model serves
and the multi-device dry-run compiles: ``attention`` sends one query
position per row (decode) to ``decode_attention``, which reads the cache
where the decode loop keeps it, long sequences to
``chunked_flash_attention`` and short ones to ``naive_attention``.  The
Pallas kernels in ``repro.kernels`` implement the same math (validated
against ``kernels.ref``); no model calls them.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import get_active_mesh


class StackedLayer(NamedTuple):
    """Layer ``i`` of an array stacked over a scan group's layers (a
    cache leaf, [n, B, ...], or held experts, [n, E, ...]): what the
    decoder's loops hand a sublayer in place of the layer's own array."""
    stack: jax.Array
    i: jax.Array

    @property
    def shape(self):
        return self.stack.shape[1:]

    def read(self):
        return jax.lax.dynamic_index_in_dim(self.stack, self.i, 0,
                                            keepdims=False)


# ----------------------------------------------------------------------------
# initializers


def dense_init(key, shape, in_axis=-2, dtype=jnp.float32):
    """LeCun-normal-ish init with fan-in on ``in_axis``."""
    fan_in = shape[in_axis] if len(shape) > 1 else shape[0]
    return (jax.random.normal(key, shape) / jnp.sqrt(fan_in)).astype(dtype)


def embed_init(key, shape, dtype=jnp.float32):
    return (jax.random.normal(key, shape) * 0.02).astype(dtype)


# ----------------------------------------------------------------------------
# norms


def rms_norm(x, scale, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dt)


def head_rms_norm(x, scale, eps=1e-6):
    """qk-norm: RMSNorm over the head_dim of [B, S, H, hd]."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dt)


# ----------------------------------------------------------------------------
# RoPE


def rope_freqs(head_dim: int, theta: float, positions, yarn=None):
    """positions: [...]; returns cos/sin of shape [..., head_dim/2].
    ``yarn`` (a ``configs.base.YarnConfig``) rescales the frequencies."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    mscale = 1.0
    if yarn is not None:
        inv = yarn_inv_freq(inv, head_dim, theta, yarn)
        mscale = (yarn_mscale(yarn.factor, yarn.mscale)
                  / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    if mscale != 1.0:
        return jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    return jnp.cos(ang), jnp.sin(ang)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor 0.1 * mscale * ln(factor) + 1."""
    if factor <= 1:
        return 1.0
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(inv, dim: int, theta: float, yarn):
    """YaRN's blend of the original frequencies ``inv`` [dim/2] (kept for
    the dims that turn more than ``beta_fast`` times over the original
    context) and the same divided by ``factor`` (for those that turn
    fewer than ``beta_slow`` times), with a linear ramp between."""

    def turns_dim(turns):
        return (dim * math.log(yarn.original_max_position
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(turns_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(turns_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return inv / yarn.factor * (1.0 - keep) + inv * keep


def apply_rope(x, cos, sin):
    """x: [B, S, H, hd]; cos/sin: [B?, S, hd/2] broadcast over heads."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:  # [S, hd/2] -> broadcast over batch and heads
        cos = cos[None, :, None, :]
        sin = sin[None, :, None, :]
    elif cos.ndim == 3:  # [B, S, hd/2]
        cos = cos[:, :, None, :]
        sin = sin[:, :, None, :]
    o1 = x1 * cos - x2 * sin
    o2 = x2 * cos + x1 * sin
    return jnp.concatenate([o1, o2], axis=-1).astype(x.dtype)


# ----------------------------------------------------------------------------
# attention (XLA paths)


def _mask_bias(q_pos, k_pos, causal: bool, window: Optional[int]):
    """Additive mask bias [*, Sq, Sk] from query/key absolute positions."""
    ok = jnp.ones((q_pos.shape[-1], k_pos.shape[-1]), dtype=bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= q_pos[:, None] - k_pos[None, :] < window
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def naive_attention(q, k, v, *, causal=True, window=None, q_offset=0,
                    k_valid=None, scale=None):
    """Reference attention.  q: [B,Sq,H,hd], k/v: [B,Sk,K,hd] (GQA K|H).

    ``q_offset``: absolute position of q[0] (decode).  ``k_valid``: number of
    valid kv entries (decode with a partially filled cache).  ``scale``:
    the softmax scale (default 1/sqrt(hd)).
    """
    B, Sq, H, hd = q.shape
    K = k.shape[2]
    hd_v = v.shape[-1]  # may differ from hd (MLA)
    G = H // K
    q = q.reshape(B, Sq, K, G, hd)
    scores = jnp.einsum("bqkgh,bskh->bkgqs", q.astype(jnp.float32),
                        k.astype(jnp.float32))
    if scale is None:
        scores = scores / jnp.sqrt(hd).astype(jnp.float32)
    else:
        scores = scores * jnp.float32(scale)
    q_pos = q_offset + jnp.arange(Sq)
    k_pos = jnp.arange(k.shape[1])
    bias = _mask_bias(q_pos, k_pos, causal, window)
    if k_valid is not None:
        bias = bias + jnp.where(k_pos[None, :] < k_valid, 0.0, -1e30)
    scores = scores + bias
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd_v).astype(q.dtype)


def chunked_flash_attention(q, k, v, *, causal=True, window=None,
                            chunk=1024, q_offset=0, k_valid=None,
                            scale=None):
    """Online-softmax attention, scanning KV chunks — the XLA 'flash' path.

    Memory is O(Sq * chunk) instead of O(Sq * Sk); numerics match
    ``naive_attention`` to ~1e-3 in bf16 (f32 accumulation throughout).

    Head-major layout: GQA k/v chunks are repeated to the full H query
    heads *inside* the scan (cheap — one chunk at a time), so the score and
    accumulator tensors keep a contiguous H dimension that GSPMD shards
    over the ``model`` axis (inherited from the wq sharding).  One shared
    kv head (MQA; MLA's latent) is never repeated: the heads are folded
    into the query rows instead.
    """
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    hd_v = v.shape[-1]
    if Sk % chunk != 0:
        return naive_attention(q, k, v, causal=causal, window=window,
                               q_offset=q_offset, k_valid=k_valid,
                               scale=scale)
    fold = H if K == 1 and H > 1 else 1
    if fold > 1:    # query row s * H + h is head h at position s
        q = q.reshape(B, Sq * H, 1, hd)
        Sq, H = Sq * H, 1
    G = H // K
    qf = q.astype(jnp.float32)
    if scale is None:
        scale = 1.0 / jnp.sqrt(hd).astype(jnp.float32)
    else:
        scale = jnp.float32(scale)
    q_pos = q_offset + (jnp.arange(Sq) // fold if fold > 1
                        else jnp.arange(Sq))

    n_chunks = Sk // chunk
    ks = k.reshape(B, n_chunks, chunk, K, k.shape[-1])
    vs = v.reshape(B, n_chunks, chunk, K, hd_v)

    def step(carry, inp):
        m, l, acc = carry
        idx, kc, vc = inp
        if G > 1:  # expand grouped kv heads to the full query-head axis
            kc = jnp.repeat(kc, G, axis=2)
            vc = jnp.repeat(vc, G, axis=2)
        k_pos = idx * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqhd,bshd->bhqs", qf,
                       kc.astype(jnp.float32)) * scale
        ok = jnp.ones((Sq, chunk), dtype=bool)
        if causal:
            ok &= k_pos[None, :] <= q_pos[:, None]
        if window is not None:
            ok &= q_pos[:, None] - k_pos[None, :] < window
        if k_valid is not None:
            ok &= (k_pos < k_valid)[None, :]
        s = s + jnp.where(ok, 0.0, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqs,bshd->bhqd", p, vc.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, hd_v), jnp.float32)
    # remat the chunk body: scan-bwd then recomputes the [B,H,Sq,chunk]
    # score/prob intermediates instead of stacking them across chunks
    step = jax.checkpoint(step,
                          policy=jax.checkpoint_policies.nothing_saveable)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (jnp.arange(n_chunks), ks.transpose(1, 0, 2, 3, 4),
         vs.transpose(1, 0, 2, 3, 4)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    out = out.transpose(0, 2, 1, 3).astype(q.dtype)
    if fold > 1:
        out = out.reshape(B, Sq // fold, fold, hd_v)
    return out


def _positions(x, start, size):
    """Positions [start, start + size) along axis 1 of ``x``: a [B, S, ...]
    array, or the layer a ``StackedLayer`` views, sliced from its stack
    so that no copy of the layer is made."""
    if isinstance(x, StackedLayer):
        zero = jnp.zeros((), jnp.int32)
        at = (x.i, zero, start) + (zero,) * (x.stack.ndim - 3)
        sizes = (1, x.stack.shape[1], size) + x.stack.shape[3:]
        return jax.lax.dynamic_slice(x.stack, at, sizes)[0]
    return jax.lax.dynamic_slice_in_dim(x, start, size, axis=1)


def decode_attention(q, k, v, *, chunk, k_valid=None, scale=None):
    """Attention of one query position per row over a cache, the decode
    path.  q: [B, 1, H, hd]; k, v: [B, S, K, hd] and [B, S, K, hd_v] (GQA
    K | H), each an array or a ``StackedLayer`` view of the layer in the
    decode loop's stacked cache.

    Each kv head's G = H / K query heads are its query rows, so no K or V
    position is repeated to the H heads.  An online softmax walks the
    cache ``chunk`` positions at a time, slicing each chunk from the cache
    where it lies; it reads the cache in one pass where ``chunk`` does
    not divide S, and on a mesh, whose devices each hold a share of the
    positions.  Cache operands enter both products in the dtype they are
    stored in; the products accumulate in f32 and the probabilities enter
    theirs as f32.  ``k_valid``: number of valid cache positions;
    ``scale``: the softmax scale (default 1/sqrt(hd)).
    """
    B, _, H, hd = q.shape
    S, K = k.shape[1:3]
    hd_v = v.shape[-1]
    G = H // K
    qg = q.reshape(B, K, G, hd)
    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    if S % chunk or get_active_mesh() is not None:
        # a mesh shards the positions, so each device reads its share in
        # one pass; a chunk sliced there would be gathered from them all
        chunk = S
    # bf16 operands multiply exactly either way; HIGHEST keeps an f32
    # operand (the probabilities, an f32 cache) from being rounded to
    # bf16 on the TPU
    highest = jax.lax.Precision.HIGHEST

    def step(j, carry):
        m, l, acc = carry
        start = j * chunk
        s = jnp.einsum("bkgd,bskd->bkgs", qg, _positions(k, start, chunk),
                       precision=highest,
                       preferred_element_type=jnp.float32) * scale
        if k_valid is not None:
            s = jnp.where(start + jnp.arange(chunk) < k_valid, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        acc = acc * corr[..., None] + jnp.einsum(
            "bkgs,bskd->bkgd", p, _positions(v, start, chunk),
            precision=highest, preferred_element_type=jnp.float32)
        return m_new, l * corr + p.sum(axis=-1), acc

    m, l, acc = jax.lax.fori_loop(
        0, S // chunk, step,
        (jnp.full((B, K, G), -jnp.inf, jnp.float32),
         jnp.zeros((B, K, G), jnp.float32),
         jnp.zeros((B, K, G, hd_v), jnp.float32)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, 1, H, hd_v).astype(q.dtype)


def attention(cfg: ModelConfig, q, k, v, *, causal=True, window=None,
              q_offset=0, k_valid=None, scale=None):
    """Dispatch: ``decode_attention`` for one query position attending
    without a mask of positions (k and v may then be ``StackedLayer``
    views); else chunked flash for long sequences, naive for short ones.
    ``scale`` is the softmax scale, 1/sqrt(head dim) by default."""
    if q.shape[1] == 1 and not causal and window is None:
        return decode_attention(q, k, v, chunk=cfg.attn_chunk,
                                k_valid=k_valid, scale=scale)
    if k.shape[1] >= cfg.flash_threshold:
        return chunked_flash_attention(q, k, v, causal=causal, window=window,
                                       chunk=cfg.attn_chunk, q_offset=q_offset,
                                       k_valid=k_valid, scale=scale)
    return naive_attention(q, k, v, causal=causal, window=window,
                           q_offset=q_offset, k_valid=k_valid, scale=scale)


# ----------------------------------------------------------------------------
# gated MLPs


def init_mlp(key, d_model, d_ff, dtype):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": dense_init(k1, (d_model, d_ff), dtype=dtype),
        "wg": dense_init(k2, (d_model, d_ff), dtype=dtype),
        "wo": dense_init(k3, (d_ff, d_model), dtype=dtype),
    }


def mlp(params, x, act: str):
    a = jnp.einsum("bsd,df->bsf", x, params["wi"])
    g = jnp.einsum("bsd,df->bsf", x, params["wg"])
    gate = jax.nn.gelu(g) if act == "gelu" else jax.nn.silu(g)
    return jnp.einsum("bsf,fd->bsd", a * gate, params["wo"])


# ----------------------------------------------------------------------------
# embedding / head


def init_embedding(key, cfg: ModelConfig, dtype):
    k1, k2 = jax.random.split(key)
    return {
        "tok": embed_init(k1, (cfg.vocab, cfg.d_model), dtype=dtype),
        "head": dense_init(k2, (cfg.d_model, cfg.vocab), dtype=dtype),
        "final_norm": jnp.zeros((cfg.d_model,), dtype=dtype),
    }


def embed(params, cfg: ModelConfig, tokens):
    x = params["tok"][tokens]
    if cfg.scale_embeddings:
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
    return x


def unembed(params, cfg: ModelConfig, x):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return jnp.einsum("bsd,dv->bsv", x, params["head"])


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def chunked_time_scan(step, init, xs, length: int, chunk: int = 64):
    """Two-level time scan for recurrences (RWKV/Mamba training).

    A flat ``lax.scan`` over S steps saves its carry (the recurrent state)
    at *every* step for the backward pass — O(S * state) memory, which is
    tens of GB for the 4k-token train cells.  Chunking saves the carry only
    at chunk boundaries (O(S/chunk * state)) and remats the inner scan, so
    the inner per-step residuals live only transiently during that chunk's
    backward.

    ``xs``: pytree of [S, ...] arrays scanned over the leading axis.
    Returns (final_carry, ys stacked to [S, ...]).
    """
    if length % chunk != 0 or length <= chunk:
        return jax.lax.scan(step, init, xs)
    n = length // chunk
    xs_c = jax.tree.map(
        lambda a: a.reshape((n, chunk) + a.shape[1:]), xs)

    def inner(carry, xc):
        return jax.lax.scan(step, carry, xc)

    inner = jax.checkpoint(inner,
                           policy=jax.checkpoint_policies.nothing_saveable)
    carry, ys = jax.lax.scan(inner, init, xs_c)
    ys = jax.tree.map(
        lambda a: a.reshape((length,) + a.shape[2:]), ys)
    return carry, ys
