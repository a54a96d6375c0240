"""DeepSeek-V2 against its plain float32 reference
(``bench/reference/deepseek_v2.py``) on the CPU at a small size, with
seeded random weights: the engine's prefill and donated decode steps,
group-limited routing, the held share of an expert layer, latent-space
decode, the dropless passes, the prefill by blocks of rows and YaRN.

The small config keeps the published structure: a dense first layer,
then expert layers of 8 experts in 4 groups, 2 groups kept, top 2, 2
shared; shares of 2 experts.  Both sides compute in float32, so they
agree to rounding (summation order), far inside the tolerances here.
"""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bench.reference import deepseek_v2 as ref  # noqa: E402
from repro.configs.base import reduced  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.models import common, layers  # noqa: E402
from repro.models.registry import build_model  # noqa: E402
from repro.serving.kvcache import pad_cache  # noqa: E402

SEED = 11
TOL = 1e-4      # float32 on both sides: rounding only


def small(first: int = 0, held: int = 0, **changes):
    cfg = reduced(get_config("deepseek-v2-236b"), **changes)
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, held_start=first, n_held=held))


def reference_cfg(cfg) -> dict:
    """The configuration file's keys for the program's ``cfg``."""
    m, e, y = cfg.mla, cfg.moe, cfg.rope_scaling
    first, held = e.held
    return {
        "layers": cfg.n_layers, "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.n_dense_layers,
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "q_lora_rank": m.q_lora_rank, "kv_lora_rank": m.kv_lora_rank,
        "qk_nope_head_dim": m.qk_nope_head_dim,
        "qk_rope_head_dim": m.qk_rope_head_dim,
        "v_head_dim": m.v_head_dim, "intermediate_size": cfg.d_ff,
        "moe_intermediate_size": e.d_ff_expert,
        "published": {"n_routed_experts": e.n_experts},
        "first_held_expert": first, "n_routed_experts": held,
        "n_shared_experts": e.n_shared, "num_experts_per_tok": e.top_k,
        "n_group": e.n_group, "topk_group": e.topk_group,
        "norm_topk_prob": e.norm_topk_prob,
        "routed_scaling_factor": e.routed_scaling_factor,
        "vocab_size": cfg.vocab, "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps, "torch_dtype": cfg.dtype,
        "rope_scaling": {"factor": y.factor,
                         "original_max_position_embeddings":
                             y.original_max_position,
                         "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                         "mscale": y.mscale,
                         "mscale_all_dim": y.mscale_all_dim}}


def rel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-9))


@pytest.mark.parametrize("first,held", [(0, 0), (2, 2)],
                         ids=["all-experts", "share-of-2"])
def test_prefill_and_decode_steps_match_the_reference(first, held):
    """Prefill, then 4 decode steps with the cache donated from one to
    the next as the engine does, each step's logits against the
    reference's full forward over the same tokens."""
    cfg = small(first, held)
    model = build_model(cfg)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(SEED))
    B, S, steps = 2, 12, 4
    toks = jax.random.randint(jax.random.PRNGKey(5), (B, S + steps), 0,
                              cfg.vocab)
    logits, caches = model.prefill(params, {"tokens": toks[:, :S]})
    want = ref.forward_logits(reference_cfg(cfg), SEED,
                              [np.asarray(toks)], steps + 1)[0]
    assert rel(logits, want[:, 0]) < TOL
    caches = pad_cache(caches, model.init_cache(B, S + steps))
    decode = jax.jit(model.decode, donate_argnums=(1,))
    for j in range(steps):
        logits, caches = decode(params, caches,
                                {"token": toks[:, S + j:S + j + 1],
                                 "pos": jnp.int32(S + j)})
        assert rel(logits, want[:, j + 1]) < TOL, j


def _np_route(logits, n_group, topk_group, top_k, scale):
    """Group-limited greedy routing as DeepSeek-V2's ``MoEGate``, in
    NumPy."""
    z = logits - logits.max(-1, keepdims=True)
    scores = np.exp(z) / np.exp(z).sum(-1, keepdims=True)
    T, E = scores.shape
    groups = scores.reshape(T, n_group, E // n_group).max(-1)
    keep = np.argsort(-groups, axis=-1, kind="stable")[:, :topk_group]
    mask = np.zeros((T, n_group), bool)
    np.put_along_axis(mask, keep, True, axis=-1)
    masked = np.where(np.repeat(mask, E // n_group, axis=1), scores, 0.0)
    idx = np.argsort(-masked, axis=-1, kind="stable")[:, :top_k]
    return np.take_along_axis(masked, idx, -1) * scale, idx


def test_group_limited_routing_against_numpy():
    """Softmax over all experts; the 2 groups with the best expert are
    kept; top 3 of their experts, weights times the scaling factor.  Row
    0 is built so that plain top-3 would take expert 5 of a dropped
    group."""
    cfg = small(n_layers=3)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, top_k=3, routed_scaling_factor=16.0, norm_topk_prob=False))
    E = cfg.moe.n_experts
    designed = np.array([[5.0, 0.1, 4.9, 0.2, 0.3, 4.85, 0.0, 0.4]])
    rand = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (31, E)))
    logits = np.concatenate([designed, 3 * rand]).astype(np.float32)
    p = {"router": jnp.eye(E, dtype=jnp.float32)}
    w, idx = layers.route(p, cfg, jnp.asarray(logits))
    nw, nidx = _np_route(logits.astype(np.float64), 4, 2, 3, 16.0)
    assert set(np.asarray(idx[0])) == {0, 2, 3}
    plain = set(np.argsort(-logits[0])[:3])
    assert plain == {0, 2, 5} != set(np.asarray(idx[0]))
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(nidx, -1))
    order = lambda i: np.argsort(i, -1)
    np.testing.assert_allclose(
        np.take_along_axis(np.asarray(w), order(idx), -1),
        np.take_along_axis(nw, order(nidx), -1), rtol=1e-5)
    rw, ridx = ref.route(jnp.asarray(logits), p["router"],
                         ref.Dims.of(reference_cfg(cfg)))
    np.testing.assert_array_equal(np.sort(ridx, -1), np.sort(nidx, -1))


def test_shares_of_the_expert_layer_add_up_to_the_uncut_layer():
    """Each share of 2 of the 8 experts routes over all 8 and gives its
    experts' part; the parts of the 4 shares, with the shared experts
    counted once, are the uncut reference layer, and their counters add
    up to every routed assignment."""
    full = small()
    key = jax.random.PRNGKey(SEED)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, full.d_model))
    p_full = layers.init_moe(key, full, jnp.float32)
    shared = common.mlp(p_full["shared"], x, full.act)
    total, counted = jnp.zeros_like(x), 0
    for first in range(0, full.moe.n_experts, 2):
        cfg = small(first, 2)
        p = layers.init_moe(key, cfg, jnp.float32)
        for k in ("wi", "wg", "wo"):
            np.testing.assert_array_equal(p[k], p_full[k][first:first + 2])
        out, load = layers.moe_ffn(p, cfg, x)
        total = total + out - shared
        counted += int(load[0])
    total = total + shared
    dims = ref.Dims.of(reference_cfg(full))
    w = {"router": p_full["router"],
         "e_wi": p_full["wi"], "e_wg": p_full["wg"], "e_wd": p_full["wo"],
         "s_wi": p_full["shared"]["wi"], "s_wg": p_full["shared"]["wg"],
         "s_wd": p_full["shared"]["wo"]}
    with jax.default_matmul_precision("highest"):
        want = ref._experts(x, w, dims, False)
    assert rel(total, want) < TOL
    assert counted == x.shape[0] * x.shape[1] * full.moe.top_k
    out, load = layers.moe_ffn(p_full, full, x)
    assert rel(out, want) < TOL
    assert int(load[1]) == full.moe.n_experts


def test_latent_decode_matches_re_expanded_attention():
    """The decode step's attention in the latent space (wk_b folded into
    the query, wv_b into the output, one shared latent head) against the
    re-expanded per-head attention prefill computes, at the new
    position, on both the naive and the chunked flash paths."""
    for threshold in (10_000, 8):
        cfg = small(flash_threshold=threshold, attn_chunk=8)
        p = layers.init_mla(jax.random.PRNGKey(3), cfg, jnp.float32)
        B, S = 2, 16
        x = jax.random.normal(jax.random.PRNGKey(4), (B, S, cfg.d_model))
        full, _ = layers.mla_sublayer(p, cfg, x, mode="prefill", cache=None,
                                      pos=None)
        _, cache = layers.mla_sublayer(p, cfg, x[:, :S - 1], mode="prefill",
                                       cache=None, pos=None)
        buf = {k: jnp.pad(v, ((0, 0), (0, 1), (0, 0)))[None]
               for k, v in cache.items()}
        views = {k: layers.StackedLayer(v, jnp.int32(0))
                 for k, v in buf.items()}
        step, _ = layers.mla_sublayer(p, cfg, x[:, S - 1:], mode="decode",
                                      cache=views, pos=jnp.int32(S - 1))
        assert rel(step[:, 0], full[:, -1]) < TOL, threshold


def test_dropless_passes_cover_every_held_assignment(monkeypatch):
    """With passes of 8 routed rows the held experts' output is the same
    as in one pass: the loop runs until the last held assignment."""
    cfg = small(2, 4)
    p = layers.init_moe(jax.random.PRNGKey(SEED), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 20, cfg.d_model))
    one, load = layers.moe_ffn(p, cfg, x)
    monkeypatch.setattr(layers, "MOE_ROWS", 8)
    many, load8 = jax.jit(lambda p, x: layers.moe_ffn(p, cfg, x))(p, x)
    assert int(load[0]) > 8
    assert rel(many, one) < TOL
    np.testing.assert_array_equal(load, load8)


def test_dropless_passes_are_differentiable(monkeypatch):
    """Training takes gradients through many passes: with passes of 8
    routed rows the gradient of the layer is the one-pass gradient."""
    cfg = small(2, 4)
    p = layers.init_moe(jax.random.PRNGKey(SEED), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 20, cfg.d_model))
    loss = lambda p, x: jnp.sum(layers.moe_ffn(p, cfg, x)[0] ** 2)
    one = jax.grad(loss, argnums=(0, 1))(p, x)
    monkeypatch.setattr(layers, "MOE_ROWS", 8)
    many = jax.jit(jax.grad(loss, argnums=(0, 1)))(p, x)
    for a, b in zip(jax.tree.leaves(many), jax.tree.leaves(one)):
        assert rel(a, b) < TOL


@pytest.mark.parametrize("inputs", ["decode-64-rows", "prefill-two-passes",
                                    "no-held-rows"])
@pytest.mark.parametrize("layer", ["first", "middle", "last"])
def test_expert_layer_reads_a_stacked_layer_in_place(monkeypatch, layer,
                                                     inputs):
    """The expert layer fed ``StackedLayer`` views of layer i of stacked
    held experts (as the decoder's inference scans hand them) gives what
    it gives fed layer i's own weights: a 64-row decode step, a prefill
    block in passes of 8 routed rows, and a layer whose held experts
    receive no row."""
    cfg = small(2, 2)
    n = 5
    i = {"first": 0, "middle": n // 2, "last": n - 1}[layer]
    stacked = jax.vmap(lambda k: layers.init_moe(k, cfg, jnp.float32))(
        jax.random.split(jax.random.PRNGKey(SEED), n))
    one = jax.tree.map(lambda a: a[i], stacked)
    if inputs == "decode-64-rows":
        x = jax.random.normal(jax.random.PRNGKey(3), (64, 1, cfg.d_model))
    else:
        x = jax.random.normal(jax.random.PRNGKey(4), (2, 20, cfg.d_model))
    if inputs == "prefill-two-passes":
        monkeypatch.setattr(layers, "MOE_ROWS", 8)
    if inputs == "no-held-rows":
        # every logit of the held experts (2, 3) below every other's
        first, held = cfg.moe.held
        r = jnp.abs(one["router"])
        one["router"] = r.at[:, first:first + held].multiply(-1.0)
        x = jnp.abs(x)
    views = dict(one, **{k: layers.StackedLayer(stacked[k], jnp.int32(i))
                         for k in ("wi", "wg", "wo")})
    ffn = jax.jit(lambda p, x: layers.moe_ffn(p, cfg, x))
    want, load = ffn(one, x)
    got, got_load = ffn(views, x)
    np.testing.assert_array_equal(got_load, load)
    assert rel(got, want) < TOL
    routed = x.shape[0] * x.shape[1] * cfg.moe.top_k
    if inputs == "no-held-rows":
        assert int(load[0]) == 0
        shared = common.mlp(one["shared"], x, cfg.act)
        assert rel(got, shared) < TOL
    else:
        assert int(load[0]) > (8 if inputs == "prefill-two-passes" else 0)
        assert int(load[0]) < routed


def test_prefill_by_blocks_of_rows_is_the_whole_batchs():
    """``prefill_tokens`` runs the batch 2 rows at a time inside one
    program: the same logits and caches, and the blocks' expert counts
    summed."""
    cfg = small(2, 2)
    whole = build_model(cfg)
    blocks = build_model(dataclasses.replace(cfg, prefill_tokens=20))
    params = whole.init_params(jax.random.PRNGKey(SEED))
    toks = jax.random.randint(jax.random.PRNGKey(6), (6, 10), 0, cfg.vocab)
    a_logits, a_cache = whole.prefill(params, {"tokens": toks})
    b_logits, b_cache = jax.jit(blocks.prefill)(params, {"tokens": toks})
    assert rel(b_logits, a_logits) < TOL
    for a, b in zip(jax.tree.leaves(a_cache), jax.tree.leaves(b_cache)):
        assert a.shape == b.shape
        if a.dtype == jnp.int32:
            np.testing.assert_array_equal(a[:, 1], b[:, 1])
            # the touched experts count once per block
            np.testing.assert_array_equal(a[:, 0, 0], b[:, 0, 0])
            assert (b[:, 0, 1] >= a[:, 0, 1]).all()
        else:
            assert rel(b, a) < TOL


def test_engine_reads_the_expert_counters_once_per_request():
    from repro.launch.serve import build_engine

    cfg = small()
    eng = build_engine("deepseek-v2-236b", cfg=cfg, max_len=32)
    B, S, n = 2, 8, 5
    toks = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0, cfg.vocab)
    eng.generate({"tokens": toks}, n)
    layers_moe = cfg.n_layers - cfg.n_dense_layers
    k = cfg.moe.top_k
    assert eng.stats.held_assignments == {
        "prefill": B * S * k * layers_moe,
        "decode": B * (n - 1) * k * layers_moe}
    assert 0 < eng.stats.experts_touched["decode"] <= \
        (n - 1) * layers_moe * cfg.moe.n_experts


def test_yarn_frequencies_and_softmax_scale():
    cfg = get_config("deepseek-v2-236b")
    m = 0.1 * 0.707 * math.log(40) + 1
    assert layers.mla_softmax_scale(cfg) == pytest.approx(
        192 ** -0.5 * m * m, rel=1e-12)
    dims = ref.Dims.of(reference_cfg(cfg))
    assert ref.softmax_scale(dims) == pytest.approx(
        layers.mla_softmax_scale(cfg), rel=1e-12)
    inv = 1.0 / (10000.0 ** (np.arange(32) / 32))
    got = np.asarray(common.yarn_inv_freq(jnp.asarray(inv, jnp.float32),
                                          64, 10000.0, cfg.rope_scaling))
    np.testing.assert_allclose(got, ref.yarn_inv_freq(dims), rtol=1e-6)
    # dims below the ramp keep their frequency, those above it are 40x
    # slower
    np.testing.assert_allclose(got[:10], inv[:10], rtol=1e-6)
    np.testing.assert_allclose(got[23:], inv[23:] / 40, rtol=1e-6)
