"""Pure-jnp oracles for every Pallas kernel (the allclose references)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal=True, window=None, k_valid=None):
    """q: [B, Sq, H, hd]; k, v: [B, Sk, K, hd]."""
    B, Sq, H, hd = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    qf = q.reshape(B, Sq, K, G, hd).astype(jnp.float32)
    s = jnp.einsum("bqkgh,bskh->bkgqs", qf, k.astype(jnp.float32))
    s = s / jnp.sqrt(hd).astype(jnp.float32)
    q_pos = jnp.arange(Sq)
    k_pos = jnp.arange(Sk)
    ok = jnp.ones((Sq, Sk), bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        ok &= q_pos[:, None] - k_pos[None, :] < window
    if k_valid is not None:
        ok &= (k_pos < k_valid)[None, :]
    s = jnp.where(ok, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def decode_attention_ref(q, k, v, k_valid):
    return flash_attention_ref(q, k, v, causal=False, k_valid=k_valid)


def moe_routing_ref(x, router_w, top_k):
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        router_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    _, idx = jax.lax.top_k(probs, top_k)
    mask = jax.nn.one_hot(idx, probs.shape[-1], dtype=jnp.float32).sum(1)
    gates = probs * mask
    return gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)


def rwkv_scan_ref(r, k, v, w, u):
    """Sequential WKV recurrence. r/k/v/w: [B, S, H, hd]; u: [H, hd]."""
    B, S, H, hd = r.shape
    rf, kf, vf, wf = (x.astype(jnp.float32) for x in (r, k, v, w))
    uf = u.astype(jnp.float32)

    def step(state, inp):
        rt, kt, vt, wt = inp
        kv = kt[..., :, None] * vt[..., None, :]
        out = jnp.einsum("bhk,bhkv->bhv", rt,
                         state + uf[None, :, :, None] * kv)
        state = wt[..., :, None] * state + kv
        return state, out

    xs = tuple(x.transpose(1, 0, 2, 3) for x in (rf, kf, vf, wf))
    _, outs = jax.lax.scan(step, jnp.zeros((B, H, hd, hd), jnp.float32), xs)
    return outs.transpose(1, 0, 2, 3).astype(r.dtype)

