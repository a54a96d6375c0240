"""Per-architecture smoke tests (assignment requirement): reduced config of
the same family, one forward/train step on CPU, output shapes + no NaNs,
plus the prefill+decode == full-forward consistency invariant."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import reduced
from repro.configs.registry import ARCHS, get_config
from repro.models.registry import build_model
from repro.serving.kvcache import pad_cache

ALL_ARCHS = sorted(ARCHS)


def make_batch(cfg, key, B, S, with_labels=True):
    toks = jax.random.randint(key, (B, S), 0, cfg.vocab)
    batch = {"tokens": toks}
    if with_labels:
        batch["labels"] = jnp.roll(toks, -1, axis=1)
    if cfg.family == "vlm":
        batch["vision_embeds"] = 0.1 * jax.random.normal(
            key, (B, cfg.vision.n_vision_tokens, cfg.d_model))
    if cfg.family == "audio":
        batch["audio_embeds"] = 0.1 * jax.random.normal(
            key, (B, S, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_train_step(arch):
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params = model.init_params(key)
    batch = make_batch(cfg, key, B=2, S=16)
    loss = model.train_loss(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), f"{arch}: NaN loss"
    # gradients exist and are finite for every leaf
    grads = jax.grad(model.train_loss)(params, batch)
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        assert np.all(np.isfinite(np.asarray(g))), f"{arch}: NaN grad {path}"


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_prefill_shapes(arch):
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    key = jax.random.PRNGKey(1)
    params = model.init_params(key)
    B, S = 2, 16
    batch = make_batch(cfg, key, B, S, with_labels=False)
    logits, caches = model.prefill(params, batch)
    assert logits.shape == (B, cfg.vocab)
    assert np.all(np.isfinite(np.asarray(logits)))
    assert len(jax.tree.leaves(caches)) > 0


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_decode_matches_full_forward(arch):
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    key = jax.random.PRNGKey(2)
    params = model.init_params(key)
    B, S = 2, 12
    batch_full = make_batch(cfg, key, B, S + 1, with_labels=False)
    if cfg.family == "audio":  # same source for both runs
        batch_full["audio_embeds"] = batch_full["audio_embeds"][:, :S]
    logits_full, _ = model.prefill(params, batch_full)

    batch_pre = dict(batch_full)
    batch_pre["tokens"] = batch_full["tokens"][:, :S]
    _, caches = model.prefill(params, batch_pre)
    ctx_len = S if cfg.family == "audio" else None
    caches = pad_cache(caches, model.init_cache(B, S + 4, ctx_len))
    logits_step, _ = model.decode(
        params, caches,
        {"token": batch_full["tokens"][:, S:S + 1], "pos": jnp.int32(S)})
    rel = (np.max(np.abs(logits_full - logits_step))
           / (np.max(np.abs(logits_full)) + 1e-9))
    assert rel < 2e-3, f"{arch}: prefill+decode diverges from full, rel={rel}"


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "hymba-1.5b"])
def test_sliding_window_ring_decode(arch):
    """Decoding past the window must keep matching the full forward."""
    cfg = reduced(get_config(arch))
    assert cfg.sliding_window is not None
    model = build_model(cfg)
    key = jax.random.PRNGKey(3)
    params = model.init_params(key)
    B = 1
    W = cfg.sliding_window
    S = 2 * W  # prompt spans two windows; ring must have wrapped
    toks = jax.random.randint(key, (B, S + 1), 0, cfg.vocab)
    logits_full, _ = model.prefill(params, {"tokens": toks})
    _, caches = model.prefill(params, {"tokens": toks[:, :S]})
    caches = pad_cache(caches, model.init_cache(B, S + 4))
    logits_step, _ = model.decode(params, caches,
                                  {"token": toks[:, S:S + 1],
                                   "pos": jnp.int32(S)})
    rel = (np.max(np.abs(logits_full - logits_step))
           / (np.max(np.abs(logits_full)) + 1e-9))
    assert rel < 2e-3, f"{arch}: ring cache broke at wrap, rel={rel}"


@pytest.mark.parametrize("arch", ["qwen3-4b", "h2o-danube-1.8b", "hymba-1.5b",
                                  "rwkv6-1.6b", "deepseek-v2-236b"])
def test_consecutive_decode_steps_match_full_forward(arch):
    """Several decode steps in a row, the cache donated from one to the
    next as the engine does, each matching the full forward: a write to
    the wrong layer or position of the stacked cache shows from the
    second step on.  Sliding-window configs start at two windows, so
    their ring wraps under the decode steps."""
    cfg = reduced(get_config(arch))
    model = build_model(cfg)
    key = jax.random.PRNGKey(4)
    params = model.init_params(key)
    B, steps = 2, 4
    S = 2 * cfg.sliding_window if cfg.sliding_window else 12
    toks = jax.random.randint(key, (B, S + steps), 0, cfg.vocab)
    _, caches = model.prefill(params, {"tokens": toks[:, :S]})
    caches = pad_cache(caches, model.init_cache(B, S + steps))
    decode = jax.jit(model.decode, donate_argnums=(1,))
    for j in range(steps):
        logits_full, _ = model.prefill(params, {"tokens": toks[:, :S + j + 1]})
        logits_step, caches = decode(
            params, caches,
            {"token": toks[:, S + j:S + j + 1], "pos": jnp.int32(S + j)})
        rel = (np.max(np.abs(logits_full - logits_step))
               / (np.max(np.abs(logits_full)) + 1e-9))
        assert rel < 2e-3, f"{arch}: decode step {j} diverges, rel={rel}"


def test_param_count_sane():
    # analytic parameter counts should be within 35% of actual init sizes
    # (analytic count skips small norm/bias tensors)
    for arch in ALL_ARCHS:
        cfg = get_config(arch)
        approx = cfg.param_count
        assert approx > 1e8, arch
    # exact check on one reduced model
    cfg = reduced(get_config("qwen3-4b"))
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    actual = sum(np.prod(p.shape) for p in jax.tree.leaves(params))
    assert actual > 0


def test_flash_attention_matches_naive():
    from repro.models import common
    key = jax.random.PRNGKey(0)
    B, S, H, K, hd = 2, 128, 4, 2, 16
    q = jax.random.normal(key, (B, S, H, hd))
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, K, hd))
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, K, hd))
    for window in [None, 32]:
        ref = common.naive_attention(q, k, v, causal=True, window=window)
        out = common.chunked_flash_attention(q, k, v, causal=True,
                                             window=window, chunk=32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)


# decode position per case; k_valid is min(pos + 1, buffer), as a ring
# buffer's, and the cache is read in chunks of 16
_DECODE_POS = {"one": 0, "mid-chunk": 22, "chunk-boundary": 31,
               "whole": 100}


@pytest.mark.parametrize("kv_case", sorted(_DECODE_POS))
@pytest.mark.parametrize("layout", ["gqa", "latent", "ring", "bf16-cache"])
def test_decode_attention_matches_naive(layout, kv_case):
    """One query position per row, not causal: ``attention`` takes the
    decode path, which reads layer 1 of 3 stacked caches through
    ``StackedLayer`` views, and agrees with ``naive_attention`` on that
    layer: GQA (8 query heads over 2 kv heads, 64 positions); one latent
    head whose keys (24 wide) are wider than its values (16), at an
    explicit scale; a ring buffer of 40 positions, which the chunk does
    not divide, read in one pass; a bf16 cache under f32 queries."""
    import dataclasses

    from repro.models import common

    cfg = dataclasses.replace(reduced(get_config("qwen3-4b")),
                              attn_chunk=16)
    B, n, H, K, S, hd, hd_v, scale = 2, 3, 8, 2, 64, 16, 16, None
    if layout == "latent":
        K, hd, scale = 1, 24, 0.37
    if layout == "ring":
        S = 40
    kv = min(_DECODE_POS[kv_case] + 1, S)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = jax.random.normal(ks[0], (B, 1, H, hd))
    k = jax.random.normal(ks[1], (n, B, S, K, hd))
    v = jax.random.normal(ks[2], (n, B, S, K, hd_v))
    if layout == "bf16-cache":
        k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    i = jnp.int32(1)
    out = jax.jit(lambda q, k, v, kv: common.attention(
        cfg, q, common.StackedLayer(k, i), common.StackedLayer(v, i),
        causal=False, k_valid=kv, scale=scale))(q, k, v, jnp.int32(kv))
    ref = common.naive_attention(q, k[1], v[1], causal=False, k_valid=kv,
                                 scale=scale)
    assert out.shape == (B, 1, H, hd_v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

