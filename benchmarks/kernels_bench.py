"""Kernel microbenchmarks: us_per_call for each Pallas kernel (interpret
mode on CPU — correctness-path timing) vs its jnp oracle."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_routing import moe_routing
from repro.kernels.rwkv_scan import rwkv_scan


def timeit(fn, *args, n=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6


def run(emit=print):
    key = jax.random.PRNGKey(0)
    B, S, H, K, hd = 1, 256, 4, 2, 64
    q = jax.random.normal(key, (B, S, H, hd), jnp.float32)
    k = jax.random.normal(key, (B, S, K, hd), jnp.float32)
    v = jax.random.normal(key, (B, S, K, hd), jnp.float32)

    fa = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=True))
    fr = jax.jit(lambda q, k, v: ref.flash_attention_ref(q, k, v))
    emit(f"kernel,flash_attention,us_per_call={timeit(fa, q, k, v):.0f},"
         f"ref_us={timeit(fr, q, k, v):.0f}")

    qd = q[:, :1]
    da = jax.jit(lambda q, k, v: decode_attention(q, k, v, S,
                                                  interpret=True))
    dr = jax.jit(lambda q, k, v: ref.decode_attention_ref(q, k, v, S))
    emit(f"kernel,decode_attention,us_per_call={timeit(da, qd, k, v):.0f},"
         f"ref_us={timeit(dr, qd, k, v):.0f}")

    x = jax.random.normal(key, (256, 64), jnp.float32)
    w = jax.random.normal(key, (64, 16), jnp.float32)
    mr = jax.jit(lambda x, w: moe_routing(x, w, 2, interpret=True))
    mrr = jax.jit(lambda x, w: ref.moe_routing_ref(x, w, 2))
    emit(f"kernel,moe_routing,us_per_call={timeit(mr, x, w):.0f},"
         f"ref_us={timeit(mrr, x, w):.0f}")

    r_ = jax.random.normal(key, (1, 128, 2, 32), jnp.float32)
    w_ = jnp.exp(-jnp.exp(jax.random.normal(key, (1, 128, 2, 32))))
    u_ = jax.random.normal(key, (2, 32), jnp.float32)
    rs = jax.jit(lambda r, k, v, w, u: rwkv_scan(r, k, v, w, u, chunk=32,
                                                 interpret=True))
    rr = jax.jit(ref.rwkv_scan_ref)
    emit(f"kernel,rwkv_scan,us_per_call={timeit(rs, r_, r_, r_, w_, u_):.0f},"
         f"ref_us={timeit(rr, r_, r_, r_, w_, u_):.0f}")

