"""Per-kernel validation: sweep shapes/dtypes, assert_allclose against the
pure-jnp oracle in interpret mode (assignment requirement), plus hypothesis
property tests on the scheduling-tick kernel."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import given, settings, st  # hypothesis or the skip shim

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_routing import moe_routing
from repro.kernels.rwkv_scan import rwkv_scan
from test_tick_parity import run_tick, tick_inputs, tick_oracle

TOL = dict(rtol=2e-3, atol=2e-3)
TOL32 = dict(rtol=2e-5, atol=2e-5)


def rand(key, shape, dtype):
    return jax.random.normal(key, shape).astype(dtype)


# ----------------------------------------------------------------------------
# flash attention: shape x dtype x mask sweep


@pytest.mark.parametrize("B,S,H,K,hd", [
    (1, 128, 4, 4, 64),     # MHA
    (2, 256, 8, 2, 64),     # GQA 4:1
    (1, 256, 4, 1, 128),    # MQA, wide head
    (2, 128, 2, 2, 32),     # small head_dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, K, hd, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (B, S, H, hd), dtype)
    k = rand(ks[1], (B, S, K, hd), dtype)
    v = rand(ks[2], (B, S, K, hd), dtype)
    out = flash_attention(q, k, v, causal=True, bq=128, bk=128,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    tol = TOL if dtype == jnp.bfloat16 else TOL32
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol)


@pytest.mark.parametrize("window", [32, 128])
def test_flash_attention_sliding_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, S, H, K, hd = 1, 256, 4, 2, 64
    q = rand(ks[0], (B, S, H, hd), jnp.float32)
    k = rand(ks[1], (B, S, K, hd), jnp.float32)
    v = rand(ks[2], (B, S, K, hd), jnp.float32)
    out = flash_attention(q, k, v, causal=True, window=window, bq=64, bk=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), **TOL32)


# ----------------------------------------------------------------------------
# decode attention


@pytest.mark.parametrize("B,S,H,K,hd,k_valid", [
    (2, 512, 8, 2, 64, 512),
    (1, 1024, 4, 1, 128, 700),   # partially filled cache
    (4, 512, 4, 4, 64, 33),      # barely-warm cache
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(B, S, H, K, hd, k_valid, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = rand(ks[0], (B, 1, H, hd), dtype)
    k = rand(ks[1], (B, S, K, hd), dtype)
    v = rand(ks[2], (B, S, K, hd), dtype)
    out = decode_attention(q, k, v, k_valid, bk=256, interpret=True)
    want = ref.decode_attention_ref(q, k, v, k_valid)
    tol = TOL if dtype == jnp.bfloat16 else TOL32
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **tol)


# ----------------------------------------------------------------------------
# moe routing


@pytest.mark.parametrize("T,D,E,k", [(256, 64, 8, 2), (128, 128, 16, 2),
                                     (256, 32, 160, 6)])
def test_moe_routing_sweep(T, D, E, k):
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    x = rand(ks[0], (T, D), jnp.float32)
    w = rand(ks[1], (D, E), jnp.float32)
    gates = moe_routing(x, w, k, bt=128, interpret=True)
    want = ref.moe_routing_ref(x, w, k)
    np.testing.assert_allclose(np.asarray(gates), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    # exactly k experts selected per token, gates sum to 1
    nz = (np.asarray(gates) > 0).sum(axis=1)
    assert (nz == k).all()
    np.testing.assert_allclose(np.asarray(gates).sum(1), 1.0, rtol=1e-5)


# ----------------------------------------------------------------------------
# rwkv scan


@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (1, 128, 2, 32, 32), (2, 256, 4, 64, 64), (1, 64, 1, 16, 16)])
def test_rwkv_scan_sweep(B, S, H, hd, chunk):
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    r = rand(ks[0], (B, S, H, hd), jnp.float32)
    k = rand(ks[1], (B, S, H, hd), jnp.float32)
    v = rand(ks[2], (B, S, H, hd), jnp.float32)
    # decay in (0, 1) like exp(-exp(w))
    w = jnp.exp(-jnp.exp(rand(ks[3], (B, S, H, hd), jnp.float32)))
    u = rand(ks[4], (H, hd), jnp.float32)
    out = rwkv_scan(r, k, v, w, u, chunk=chunk, interpret=True)
    want = ref.rwkv_scan_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_rwkv_scan_matches_model_layer():
    """The kernel must agree with the model's sequential WKV recurrence."""
    from repro.configs.base import reduced
    from repro.configs.registry import get_config
    cfg = reduced(get_config("rwkv6-1.6b"))
    B, S = 2, 64
    hd = cfg.ssm.rwkv_head_dim
    H = cfg.d_model // hd
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    r = rand(ks[0], (B, S, H, hd), jnp.float32)
    k = rand(ks[1], (B, S, H, hd), jnp.float32)
    v = rand(ks[2], (B, S, H, hd), jnp.float32)
    w = jnp.exp(-jnp.exp(rand(ks[3], (B, S, H, hd), jnp.float32)))
    u = rand(ks[4], (H, hd), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(rwkv_scan(r, k, v, w, u, chunk=16, interpret=True)),
        np.asarray(ref.rwkv_scan_ref(r, k, v, w, u)), rtol=2e-4, atol=2e-4)


# ----------------------------------------------------------------------------
# pure-*numpy* oracles (independent of the jnp ref module) + the padding
# edges, so moe_routing / rwkv_scan / the ops wrappers stop being dark


def _moe_np(x, w, top_k):
    """Numpy mirror of the kernel: softmax over router logits, iterative
    top-k with first-index tie-breaks, renormalized over the selection."""
    logits = np.asarray(x, np.float32) @ np.asarray(w, np.float32)
    e = np.exp(logits - logits.max(-1, keepdims=True))
    probs = e / e.sum(-1, keepdims=True)
    remaining = probs.copy()
    sel = np.zeros_like(probs)
    for _ in range(top_k):
        pick = np.zeros_like(probs, bool)
        pick[np.arange(len(probs)), remaining.argmax(-1)] = True
        pick &= remaining > 0
        sel += np.where(pick, probs, 0.0)
        remaining[pick] = -1.0
    return sel / np.maximum(sel.sum(-1, keepdims=True), 1e-9)


def _rwkv_np(r, k, v, w, u):
    """Numpy mirror of the sequential WKV recurrence."""
    r, k, v, w = (np.asarray(a, np.float32) for a in (r, k, v, w))
    u = np.asarray(u, np.float32)
    B, S, H, hd = r.shape
    state = np.zeros((B, H, hd, hd), np.float32)
    out = np.zeros_like(r)
    for t in range(S):
        kv = k[:, t, :, :, None] * v[:, t, :, None, :]
        out[:, t] = np.einsum(
            "bhk,bhkv->bhv", r[:, t], state + u[None, :, :, None] * kv)
        state = w[:, t, :, :, None] * state + kv
    return out


@pytest.mark.parametrize("T,D,E,k,bt", [
    (96, 32, 8, 2, 128),     # T < bt: block clamps to the full batch
    (192, 64, 16, 1, 64),    # multi-block, top-1
    (128, 32, 6, 4, 128),    # k large relative to E
])
def test_moe_routing_vs_numpy_oracle(T, D, E, k, bt):
    rng = np.random.default_rng(42)
    x = rng.standard_normal((T, D)).astype(np.float32)
    w = rng.standard_normal((D, E)).astype(np.float32)
    got = np.asarray(moe_routing(x, w, k, bt=bt, interpret=True))
    np.testing.assert_allclose(got, _moe_np(x, w, k),
                               rtol=1e-4, atol=1e-5)
    assert ((got > 0).sum(axis=1) == k).all()


@pytest.mark.parametrize("B,S,H,hd,chunk", [
    (1, 48, 2, 16, 64),      # S < chunk: single clamped chunk
    (2, 96, 1, 32, 32),      # multi-chunk, state carried across
])
def test_rwkv_scan_vs_numpy_oracle(B, S, H, hd, chunk):
    rng = np.random.default_rng(7)
    shape = (B, S, H, hd)
    r = rng.standard_normal(shape).astype(np.float32)
    k = rng.standard_normal(shape).astype(np.float32)
    v = rng.standard_normal(shape).astype(np.float32)
    w = np.exp(-np.exp(rng.standard_normal(shape))).astype(np.float32)
    u = rng.standard_normal((H, hd)).astype(np.float32)
    got = np.asarray(rwkv_scan(r, k, v, w, u, chunk=chunk,
                               interpret=True))
    np.testing.assert_allclose(got, _rwkv_np(r, k, v, w, u),
                               rtol=2e-4, atol=2e-4)


# ----------------------------------------------------------------------------
# ops.py: the jit'd public wrappers (interpret auto-resolves off-TPU)


def test_ops_wrappers_match_references():
    from repro.kernels import ops

    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    B, S, H, K, hd = 1, 128, 4, 2, 32
    q = rand(ks[0], (B, S, H, hd), jnp.float32)
    k = rand(ks[1], (B, S, K, hd), jnp.float32)
    v = rand(ks[2], (B, S, K, hd), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.flash_attention(q, k, v, causal=True)),
        np.asarray(ref.flash_attention_ref(q, k, v, causal=True)),
        **TOL32)

    qd = rand(ks[3], (B, 1, H, hd), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.decode_attention(qd, k, v, 100, bk=64)),
        np.asarray(ref.decode_attention_ref(qd, k, v, 100)), **TOL32)

    rng = np.random.default_rng(11)
    x = rng.standard_normal((128, 32)).astype(np.float32)
    w = rng.standard_normal((32, 8)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(ops.moe_routing(x, w, 2)), _moe_np(x, w, 2),
        rtol=1e-4, atol=1e-5)

    shape = (1, 64, 2, 16)
    r_ = rng.standard_normal(shape).astype(np.float32)
    k_ = rng.standard_normal(shape).astype(np.float32)
    v_ = rng.standard_normal(shape).astype(np.float32)
    w_ = np.exp(-np.exp(rng.standard_normal(shape))).astype(np.float32)
    u_ = rng.standard_normal((2, 16)).astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(ops.rwkv_scan(r_, k_, v_, w_, u_, chunk=32)),
        _rwkv_np(r_, k_, v_, w_, u_), rtol=2e-4, atol=2e-4)


# ----------------------------------------------------------------------------
# the scheduling-tick kernel (the paper's Eq. 2-4 at fleet scale):
# placement parity against the numpy transcription of SynergAI._place


def _random_tick(J, W, seed, infeasible, rem_hi):
    """Random pool rows (``infeasible`` share inf), budgets from -5 to
    ``rem_hi`` (straddling zero), live depth penalties, busy waits and a
    partly open fleet."""
    rng = np.random.default_rng(seed)
    t = rng.uniform(0.5, 30, (J, W))
    t[rng.random((J, W)) < infeasible] = np.inf
    return tick_inputs(
        t, rng.uniform(-5, rem_hi, J),
        pen=np.where(rng.random(W) < 0.3, 2.0, 1.0),
        bw=rng.uniform(0, 10, W), avail=rng.random(W) < 0.6)


def _paper_tick(cd):
    """The rows ``estimate_matrix`` gives the DH-FH paper experiment on
    its 3-pool testbed, every pool open."""
    from repro.core.estimator import estimate_matrix
    from repro.core.job import make_experiment
    jobs = make_experiment(cd, "DH", "FH", seed=11)
    s = estimate_matrix(cd, jobs, ["cloud-pod", "edge-large",
                                   "edge-small"], 100.0)
    return tick_inputs(s.t_estimated, s.t_remaining,
                       bw=np.array([0.0, 3.0, 7.0]))


@pytest.mark.parametrize("case", ["random-40x16-bj8",
                                  "random-300x17-bj128", "paper-rows"])
def test_scheduler_tick_matches_numpy_placement(configdict, case):
    if case == "random-40x16-bj8":
        inp, bj = _random_tick(40, 16, seed=3, infeasible=0.15,
                               rem_hi=40), 8
    elif case == "random-300x17-bj128":
        # tight budgets: open workers outnumber the jobs they accept, so
        # doomed jobs place too, by busy wait plus estimate
        inp, bj = _random_tick(300, 17, seed=0, infeasible=0.2,
                               rem_hi=2), 128
    else:
        inp, bj = _paper_tick(configdict), 8
    assign, order = run_tick(inp, bj=bj)
    want = tick_oracle(inp, np.float32)
    np.testing.assert_array_equal(order, want["order"])
    np.testing.assert_array_equal(assign, want["assign"])
    assert (assign >= 0).any()          # something actually placed
    assert want["doom"].any() and not want["doom"].all()
    if case == "random-300x17-bj128":
        placed = assign >= 0
        assert (placed & want["doom"]).any()
        assert (placed & ~want["doom"]).any()


_PROP_J, _PROP_W = 40, 8        # one padded shape: one compile


@settings(max_examples=25, deadline=None)
@given(j=st.integers(1, _PROP_J), w=st.integers(1, _PROP_W),
       seed=st.integers(0, 999))
def test_scheduler_tick_property(j, w, seed):
    rng = np.random.default_rng(seed)
    t = rng.uniform(0.1, 50, (j, w))
    t[rng.random((j, w)) < 0.2] = np.inf
    inp = tick_inputs(t, rng.uniform(-10, 60, j),
                      pen=np.where(rng.random(w) < 0.3, 1.5, 1.0),
                      bw=rng.uniform(0, 10, w), avail=rng.random(w) < 0.5)
    assign, order = run_tick(inp, bj=8, Jp=_PROP_J, Wp=_PROP_W)
    want = tick_oracle(inp, np.float32)
    placed = assign[assign >= 0]
    # each worker takes at most one job, and only an open one
    assert len(set(placed)) == len(placed)
    assert inp["avail"][placed].all()
    # an assigned job was eligible on its worker
    ji = np.nonzero(assign >= 0)[0]
    assert want["elig"][ji, assign[ji]].all()
    # the order is the stable (doomed, urgency) sort
    np.testing.assert_array_equal(order,
                                  np.lexsort((want["urg"], want["doom"])))
