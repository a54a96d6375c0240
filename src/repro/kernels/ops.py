"""jit'd public wrappers over the Pallas kernels.

``interpret=None`` (the default everywhere) resolves through
``resolve_interpret``: the lowered Mosaic kernels on a TPU backend, the
Pallas interpreter on any other, which is how the tests run them on CPU.
A caller that must not fall back checks the resolved value.
"""

from __future__ import annotations

from functools import partial

import jax

from repro.kernels import (decode_attention as _da, flash_attention as _fa,
                           moe_routing as _mr, rwkv_scan as _rs)
from repro.kernels.scheduler_score import resolve_interpret


@partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                   "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, bq=128, bk=128,
                    interpret=None):
    interpret = resolve_interpret(interpret)
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               bq=bq, bk=bk, interpret=interpret)


@partial(jax.jit, static_argnames=("bk", "interpret"))
def decode_attention(q, k, v, k_valid, *, bk=512, interpret=None):
    interpret = resolve_interpret(interpret)
    return _da.decode_attention(q, k, v, k_valid, bk=bk,
                                interpret=interpret)


@partial(jax.jit, static_argnames=("top_k", "bt", "interpret"))
def moe_routing(x, router_w, top_k, *, bt=128, interpret=None):
    interpret = resolve_interpret(interpret)
    return _mr.moe_routing(x, router_w, top_k, bt=bt, interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv_scan(r, k, v, w, u, *, chunk=64, interpret=None):
    interpret = resolve_interpret(interpret)
    return _rs.rwkv_scan(r, k, v, w, u, chunk=chunk, interpret=interpret)

