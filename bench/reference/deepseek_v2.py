"""Plain float32 reference of DeepSeek-V2 (arXiv:2405.04434; the
``config.json`` of deepseek-ai/DeepSeek-V2) on a chip's share of its
experts.  It reads only the benchmark's configuration and draws the
weights itself.

Each layer: RMSNorm, multi-head latent attention, RMSNorm, then a SwiGLU
MLP (the first ``first_k_dense_replace`` layers) or the expert layer; a
final RMSNorm and an untied output head.

* Attention re-expands keys and values per head: the query is the
  normed ``q_lora_rank`` latent times ``wq_b`` (128 nope and 64 rope dims
  a head), the key's nope part the normed ``kv_lora_rank`` latent times
  ``wk_b``, its rope part one shared 64-dim key, the value the latent
  times ``wv_b``.  Rope on the 64 dims with YaRN's frequencies
  (``rope_scaling``); causal softmax at 1/sqrt(192) times YaRN's mscale
  squared.
* The expert layer: softmax over all router logits; a group's score is
  its best expert's; the experts of the ``topk_group`` best of
  ``n_group`` groups are eligible; the top ``num_experts_per_tok`` of
  those are chosen, weighted by their score times
  ``routed_scaling_factor``.  Each held expert (``first_held_expert`` and
  the ``n_routed_experts`` after it) adds its weighted output to the
  tokens that chose it, with no capacity and no drop; the absent experts
  add nothing, as they would on other chips.  The shared experts (one
  MLP of ``n_shared_experts`` times the expert width) add to every
  token.

Departures from the published model: rope rotates the two halves of the
64 dims (the program's layout); the published model rotates interleaved
pairs, which on random weights is a fixed permutation of the same
arithmetic.  Only the held experts' part of each expert layer is
computed, as in the program's share.

Weights.  The cell's weights are the seeded initialization the program's
``build_engine`` runs, written out here as a recipe over ``jax.random``.
From ``PRNGKey(seed)`` the keys split into the embedding key and one key
per scan group (the dense layers, then the expert layers); a group's
key splits into one key per layer; a layer key splits in four
(attention, MLP or experts, two unused); the attention key in six
(``wq_a``, ``wq_b``, ``wkv_a``, ``wk_b``, ``wv_b``, ``wo``); the MLP key
in three (up, gate, down); the expert key in five (router, up, gate,
down, shared), and expert e's up, gate and down matrices are drawn from
the e-th key of a split of each of those keys into one key per routed
expert (so a share's experts are the uncut layer's); the shared key
splits in three (up, gate, down).  A matrix is a standard normal over the square root of its
fan-in (the first axis, but the model width for ``wo`` and the
intermediate width for a down matrix), the token table a standard
normal times 0.02, every norm scale 0; each is rounded to the
configuration's dtype except the router, which the program keeps in
float32.  A norm multiplies by ``1 + scale``.

Precision.  Every matrix product runs at ``Precision.HIGHEST`` on
float32.  ``quantize=True`` is the control: the operands of every
projection but the router's (weights per output column, activations per
token) are rounded to float8 e4m3 with an absolute-maximum scale, the
precision one step below the configuration's bfloat16.

The reference runs layer by layer: one layer's weights exist at a time,
and attention is taken in blocks of query rows.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.decoder import (_argmax, _fp8, _gaps, _padded,
                                     padded_positions)

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256


@dataclasses.dataclass(frozen=True)
class Dims:
    layers: int
    dense_layers: int
    d: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    ff: int
    ff_expert: int
    experts: int           # the router's width
    first_held: int
    held: int
    shared: int
    top_k: int
    n_group: int
    topk_group: int
    norm_topk: bool
    scaling: float
    vocab: int
    theta: float
    eps: float
    yarn: tuple            # (factor, original, beta_fast, beta_slow,
    dtype: str             #  mscale, mscale_all_dim)

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        y = cfg["rope_scaling"]
        published = cfg.get("published", {})
        return cls(
            cfg.get("layers", cfg["num_hidden_layers"]),
            cfg["first_k_dense_replace"], cfg["hidden_size"],
            cfg["num_attention_heads"], cfg["q_lora_rank"],
            cfg["kv_lora_rank"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            published.get("n_routed_experts", cfg["n_routed_experts"]),
            cfg.get("first_held_expert", 0), cfg["n_routed_experts"],
            cfg["n_shared_experts"], cfg["num_experts_per_tok"],
            cfg["n_group"], cfg["topk_group"], bool(cfg["norm_topk_prob"]),
            float(cfg["routed_scaling_factor"]), cfg["vocab_size"],
            float(cfg["rope_theta"]), float(cfg["rms_norm_eps"]),
            (float(y["factor"]), int(y["original_max_position_embeddings"]),
             float(y["beta_fast"]), float(y["beta_slow"]),
             float(y["mscale"]), float(y["mscale_all_dim"])),
            cfg["torch_dtype"])

    def kind(self, i: int) -> str:
        return "dense" if i < self.dense_layers else "moe"


# ---------------------------------------------------------------------------
# weights


def _normal(key, shape, fan_in):
    return jax.random.normal(key, shape) / jnp.sqrt(fan_in)


def layer_keys(seed: int, dims: Dims):
    """The embedding key and each layer's key."""
    groups = [n for n in (dims.dense_layers,
                          dims.layers - dims.dense_layers) if n]
    k_embed, *gkeys = jax.random.split(jax.random.PRNGKey(seed),
                                       len(groups) + 1)
    keys = []
    for n, gk in zip(groups, gkeys):
        keys.extend(jax.random.split(gk, n))
    return k_embed, keys


@functools.partial(jax.jit, static_argnums=(1, 2))
def _layer_weights(key, dims: Dims, kind: str):
    dt = jnp.dtype(dims.dtype)
    D, H = dims.d, dims.heads
    k_attn, k_ffn, _, _ = jax.random.split(key, 4)
    a = jax.random.split(k_attn, 6)
    w = {"wq_a": _normal(a[0], (D, dims.q_rank), D),
         "wq_b": _normal(a[1], (dims.q_rank, H, dims.nope + dims.rope),
                         dims.q_rank),
         "wkv_a": _normal(a[2], (D, dims.kv_rank + dims.rope), D),
         "wk_b": _normal(a[3], (dims.kv_rank, H, dims.nope), dims.kv_rank),
         "wv_b": _normal(a[4], (dims.kv_rank, H, dims.v_dim), dims.kv_rank),
         "wo": _normal(a[5], (H, dims.v_dim, D), D)}
    if kind == "dense":
        m = jax.random.split(k_ffn, 3)
        w.update(wi=_normal(m[0], (D, dims.ff), D),
                 wg=_normal(m[1], (D, dims.ff), D),
                 wd=_normal(m[2], (dims.ff, D), dims.ff))
    else:
        m = jax.random.split(k_ffn, 5)
        F = dims.ff_expert

        def experts(k, shape, fan):
            ks = jax.random.split(k, dims.experts)
            ks = ks[dims.first_held:dims.first_held + dims.held]
            return jax.vmap(lambda kk: _normal(kk, shape, fan))(ks)

        s = jax.random.split(m[4], 3)
        Fs = dims.shared * F
        w.update(e_wi=experts(m[1], (D, F), D),
                 e_wg=experts(m[2], (D, F), D),
                 e_wd=experts(m[3], (F, D), F),
                 s_wi=_normal(s[0], (D, Fs), D),
                 s_wg=_normal(s[1], (D, Fs), D),
                 s_wd=_normal(s[2], (Fs, D), Fs))
    w = {k: v.astype(dt).astype(jnp.float32) for k, v in w.items()}
    if kind == "moe":
        w["router"] = _normal(m[0], (D, dims.experts), D)
    return w


@functools.partial(jax.jit, static_argnums=(1,))
def _token_table(key, dims: Dims):
    k_tok, _ = jax.random.split(key)
    return (jax.random.normal(k_tok, (dims.vocab, dims.d)) * 0.02).astype(
        jnp.dtype(dims.dtype))


@functools.partial(jax.jit, static_argnums=(1,))
def _head(key, dims: Dims):
    _, k_head = jax.random.split(key)
    return _normal(k_head, (dims.d, dims.vocab), dims.d).astype(
        jnp.dtype(dims.dtype)).astype(jnp.float32)


def layer_weights(seed: int, dims: Dims, i: int) -> dict:
    """Layer ``i``'s weights as float32 (for tests that set them beside
    the program's)."""
    return _layer_weights(layer_keys(seed, dims)[1][i], dims, dims.kind(i))


# ---------------------------------------------------------------------------
# the forward pass


def _proj(x, w, spec, quantize):
    """x @ w by einsum ``spec``; the contracted axes of x are its last,
    those of w its first."""
    if quantize:
        x = _fp8(x, -1)
        w = _fp8(w, 0)
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dims: Dims) -> np.ndarray:
    """YaRN (DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``): the
    frequencies of the dims that turn fewer than ``beta_slow`` times
    over the original context are divided by ``factor``, those that turn
    more than ``beta_fast`` times kept, with a linear ramp between."""
    factor, orig, fast, slow, _, _ = dims.yarn
    dim, base = dims.rope, dims.theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)

    def turns_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(turns_dim(fast)), 0)
    high = min(math.ceil(turns_dim(slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp
    return (extra / factor * (1 - keep) + extra * keep).astype(np.float32)


def softmax_scale(dims: Dims) -> float:
    factor, _, _, _, _, mscale_all = dims.yarn
    return (dims.nope + dims.rope) ** -0.5 * _yarn_mscale(
        factor, mscale_all) ** 2


def _rope(x, dims: Dims):
    """x: [B, S, H, rope] at positions 0..S-1, halves rotated."""
    factor, _, _, _, mscale, mscale_all = dims.yarn
    m = _yarn_mscale(factor, mscale) / _yarn_mscale(factor, mscale_all)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * \
        yarn_inv_freq(dims)
    cos = (jnp.cos(ang) * m)[None, :, None]
    sin = (jnp.sin(ang) * m)[None, :, None]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _mlp(h, wi, wg, wd, quantize):
    up = _proj(h, wi, "bsd,df->bsf", quantize)
    gate = _proj(h, wg, "bsd,df->bsf", quantize)
    return _proj(up * jax.nn.silu(gate), wd, "bsf,fd->bsd", quantize)


def route(h, router, dims: Dims):
    """h: [..., D] -> (weights, experts) [..., top_k], group-limited."""
    scores = jax.nn.softmax(jnp.einsum("...d,de->...e", h, router,
                                       precision=HI), axis=-1)
    groups = scores.reshape(scores.shape[:-1] + (dims.n_group, -1))
    _, best = jax.lax.top_k(groups.max(axis=-1), dims.topk_group)
    kept = jnp.zeros(groups.shape[:-1], bool)
    for j in range(dims.topk_group):
        kept = kept | (jnp.arange(dims.n_group) == best[..., j:j + 1])
    scores = jnp.where(kept[..., None], groups, 0.0).reshape(scores.shape)
    w, idx = jax.lax.top_k(scores, dims.top_k)
    if dims.norm_topk:
        return w / (w.sum(-1, keepdims=True) + 1e-20), idx
    return w * dims.scaling, idx


def _experts(h, w, dims: Dims, quantize):
    """The held experts' weighted outputs plus the shared experts'."""
    weights, idx = route(h, w["router"], dims)
    out = _mlp(h, w["s_wi"], w["s_wg"], w["s_wd"], quantize)
    for e in range(dims.held):
        gate = jnp.sum(jnp.where(idx == dims.first_held + e, weights, 0.0),
                       axis=-1)
        out = out + gate[..., None] * _mlp(h, w["e_wi"][e], w["e_wg"][e],
                                           w["e_wd"][e], quantize)
    return out


def _attention(h, w, dims: Dims, quantize):
    B, S, _ = h.shape
    q = _proj(_rms(_proj(h, w["wq_a"], "bsd,dr->bsr", quantize), dims.eps),
              w["wq_b"], "bsr,rhk->bshk", quantize)
    kv = _proj(h, w["wkv_a"], "bsd,dr->bsr", quantize)
    ckv = _rms(kv[..., :dims.kv_rank], dims.eps)
    k_rope = _rope(kv[..., None, dims.kv_rank:], dims)
    k_nope = _proj(ckv, w["wk_b"], "bsr,rhk->bshk", quantize)
    v = _proj(ckv, w["wv_b"], "bsr,rhv->bshv", quantize)
    q = jnp.concatenate([q[..., :dims.nope], _rope(q[..., dims.nope:], dims)],
                        -1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, k_nope.shape[:3] + (dims.rope,))], -1)
    scale = softmax_scale(dims)
    outs = []
    for lo in range(0, S, Q_BLOCK):
        hi = min(S, lo + Q_BLOCK)
        s = jnp.einsum("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, :hi],
                       precision=HI) * scale
        causal = jnp.arange(hi)[None, :] <= jnp.arange(lo, hi)[:, None]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v[:, :hi],
                               precision=HI))
    o = jnp.concatenate(outs, axis=1)
    return _proj(o.reshape(B, S, -1), w["wo"].reshape(-1, dims.d),
                 "bsf,fd->bsd", quantize)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _layer(x, w, dims: Dims, kind: str, quantize: bool):
    x = x + _attention(_rms(x, dims.eps), w, dims, quantize)
    h = _rms(x, dims.eps)
    if kind == "dense":
        return x + _mlp(h, w["wi"], w["wg"], w["wd"], quantize)
    return x + _experts(h, w, dims, quantize)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _logits(x, head, n: int, eps: float, quantize: bool):
    """Logits [B, n, V] of the last ``n`` positions."""
    return _proj(_rms(x[:, -n:], eps), head, "bsd,dv->bsv", quantize)


def forward_logits(cfg: dict, seed: int, seqs, n, quantize=False):
    """Teacher-forced logits of the last ``n`` positions of each
    sequence in ``seqs`` ([B, T] int32 arrays), layer by layer; ``n`` is
    one count for all or a list of one per sequence."""
    dims = Dims.of(cfg)
    k_embed, keys = layer_keys(seed, dims)
    table = _token_table(k_embed, dims)
    xs = [table[jnp.asarray(s)].astype(jnp.float32) for s in seqs]
    del table
    with jax.default_matmul_precision("highest"):
        for i in range(dims.layers):
            w = _layer_weights(keys[i], dims, dims.kind(i))
            xs = [_layer(x, w, dims, dims.kind(i), quantize) for x in xs]
            del w
        head = _head(k_embed, dims)
        ns = n if isinstance(n, (list, tuple)) else [n] * len(xs)
        return [_logits(x, head, k, dims.eps, quantize)
                for x, k in zip(xs, ns)]


def served_gaps(cfg: dict, seed: int, prompts, served, control=False,
                positions: int = 0):
    """For each request (prompts [B, P], served tokens [B, n]): the gap
    of every served token below the reference's best logit at its
    position, [B, n] float32.  With ``control``, also the gaps of the
    tokens that the fp8 control puts first at the same positions.

    Each sequence is the prompt and the served tokens but the last,
    padded at its end to ``positions`` served positions (at least n,
    rounded up to whole blocks), so that the reference runs one shape per
    prompt length; causal attention keeps the padding out of every
    position compared."""
    seqs, ns, m = [], [], []
    for p, s in zip(prompts, served):
        n = s.shape[1]
        mm = padded_positions(max(n, positions))
        pad = np.zeros((s.shape[0], mm - n), np.int32)
        seqs.append(np.concatenate([p, s[:, :-1], pad], axis=1).astype(
            np.int32))
        ns.append(n)
        m.append(mm)
    ref = forward_logits(cfg, seed, seqs, m)
    gaps = [np.asarray(_gaps(r, jnp.asarray(_padded(s, mm))))[:, :n]
            for r, s, n, mm in zip(ref, served, ns, m)]
    if not control:
        return gaps, None
    low = forward_logits(cfg, seed, seqs, m, quantize=True)
    cgaps = [np.asarray(_gaps(r, _argmax(c)))[:, :n]
             for r, c, n in zip(ref, low, ns)]
    return gaps, cgaps
