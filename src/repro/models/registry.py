"""Unified Model API over all assigned architectures.

``build_model(arch_or_cfg)`` returns a ``Model`` whose pure functions are the
things the launcher lowers:

    train_loss(params, batch)            -> scalar loss
    prefill(params, batch)               -> (last_logits [B,V], caches)
    decode(params, caches, batch)        -> (logits [B,V], caches)
    expert_load(caches)                  -> per-layer expert counters
    init_params(key)                     -> pytree
    init_cache(batch, buf_len)           -> caches pytree
    input_specs(shape_cell)              -> batch pytree of ShapeDtypeStruct

The modality frontends ([vlm]/[audio]) are stubs by assignment: the batch
carries precomputed ``vision_embeds`` / ``audio_embeds``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeCell
from repro.configs.registry import get_config
from repro.models import common, decoder
from repro.models.common import dtype_of


def cross_entropy(logits, labels):
    """logits: [B, S, V] (any float dtype), labels: [B, S] int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


CE_CHUNK = 512  # sequence tokens per loss chunk


def chunked_ce_loss(params, cfg, x, labels):
    """Cross-entropy without materializing full [B, S, V] logits.

    The unembed + logsumexp runs per sequence chunk under remat, so the
    peak logits buffer is S/CE_CHUNK times smaller — this is what lets the
    256k-vocab train cells fit 16 GB/chip.
    """
    B, S, D = x.shape
    n = S // CE_CHUNK if (S % CE_CHUNK == 0 and S > CE_CHUNK) else 1
    if n == 1:
        logits = common.unembed(params["embed"], cfg, x)
        return cross_entropy(logits, labels)
    xc = x.reshape(B, n, S // n, D).transpose(1, 0, 2, 3)
    yc = labels.reshape(B, n, S // n).transpose(1, 0, 2)

    def body(tot, inp):
        xi, yi = inp
        logits = common.unembed(params["embed"], cfg, xi).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yi[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(lse - gold), None

    body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, yc))
    return total / (B * S)


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init_params: Callable[[Any], Any]
    train_loss: Callable[[Any, Any], Any]
    prefill: Callable[[Any, Any], Any]
    decode: Callable[[Any, Any, Any], Any]
    init_cache: Callable[[int, int], Any]
    input_specs: Callable[[ShapeCell], Any]
    # the cache's per-layer expert counters (``decoder.expert_load``)
    expert_load: Callable[[Any], list] = lambda caches: []


# ----------------------------------------------------------------------------
# decoder-only families (dense / moe / ssm / hybrid / vlm)


def _ctx_of(cfg, batch):
    if cfg.family == "vlm":
        return batch["vision_embeds"]
    return None


def _build_decoder_model(cfg: ModelConfig) -> Model:
    def init_params(key):
        return decoder.init_decoder(key, cfg)

    def train_loss(params, batch):
        x = common.embed(params["embed"], cfg, batch["tokens"])
        x, _ = decoder.decoder_stack(params, cfg, x, mode="train",
                                     ctx=_ctx_of(cfg, batch))
        return chunked_ce_loss(params, cfg, x, batch["labels"])

    def prefill_all(params, batch):
        x = common.embed(params["embed"], cfg, batch["tokens"])
        x, caches = decoder.decoder_stack(params, cfg, x, mode="prefill",
                                          ctx=_ctx_of(cfg, batch))
        logits = common.unembed(params["embed"], cfg, x[:, -1:, :])
        return logits[:, 0, :], caches

    def prefill(params, batch):
        B, S = batch["tokens"].shape
        if cfg.prefill_tokens and B * S > cfg.prefill_tokens:
            # the most rows that divide the batch and fit the tokens
            rows = max([r for r in range(1, B + 1)
                        if B % r == 0 and r * S <= cfg.prefill_tokens]
                       or [1])
            return prefill_by_rows(prefill_all, params, batch, rows)
        return prefill_all(params, batch)

    def decode(params, caches, batch):
        x = common.embed(params["embed"], cfg, batch["token"])
        x, caches = decoder.decoder_stack(params, cfg, x, mode="decode",
                                          caches=caches, pos=batch["pos"],
                                          ctx=None)
        logits = common.unembed(params["embed"], cfg, x)
        return logits[:, 0, :], caches

    def init_cache(batch_size, buf_len, ctx_len=None):
        del ctx_len  # vlm ctx length is fixed by the vision stub
        n_ctx = cfg.vision.n_vision_tokens if cfg.vision else 0
        return decoder.init_decoder_cache(cfg, batch_size, buf_len, n_ctx)

    def input_specs(shape: ShapeCell):
        B, S = shape.global_batch, shape.seq_len
        dt = dtype_of(cfg)
        tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
        if shape.kind == "train":
            batch = {"tokens": tok, "labels": tok}
        elif shape.kind == "prefill":
            batch = {"tokens": tok}
        else:
            batch = {"token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                     "pos": jax.ShapeDtypeStruct((), jnp.int32)}
        if cfg.family == "vlm" and shape.kind in ("train", "prefill"):
            batch["vision_embeds"] = jax.ShapeDtypeStruct(
                (B, cfg.vision.n_vision_tokens, cfg.d_model), dt)
        return batch

    return Model(cfg, init_params, train_loss, prefill, decode, init_cache,
                 input_specs, decoder.expert_load)


def prefill_by_rows(prefill, params, batch, rows: int):
    """``prefill`` of a batch taken ``rows`` rows at a time inside one
    program, so that its activations are those of ``rows`` rows.  Rows are
    independent (dropless experts), so the logits and caches are those of
    the whole batch: each block's are written into the whole batch's
    buffers at its rows, and its ``moe_load`` counts are summed."""
    B = batch["tokens"].shape[0]
    if B % rows:
        raise ValueError(f"prefill of {B} rows in blocks of {rows}")
    block = lambda b: jax.tree.map(
        lambda a: jax.lax.dynamic_slice_in_dim(a, b * rows, rows, 0), batch)
    shapes = jax.eval_shape(prefill, params, block(0))
    counted = lambda path: path[-1] == jax.tree_util.DictKey("moe_load")
    # logits [rows, V] take rows on axis 0; stacked cache leaves
    # [n, rows, ...] on axis 1
    axis = lambda path: 0 if len(path) == 1 else 1

    def whole(path, s):
        if counted(path):
            return jnp.zeros(s.shape, s.dtype)
        shape = list(s.shape)
        shape[axis(path)] = B
        return jnp.zeros(shape, s.dtype)

    def body(b, acc):
        out = prefill(params, block(b))
        return jax.tree_util.tree_map_with_path(
            lambda path, a, o: a + o if counted(path) else
            jax.lax.dynamic_update_slice_in_dim(a, o, b * rows, axis(path)),
            acc, out)

    init = jax.tree_util.tree_map_with_path(whole, shapes)
    return jax.lax.fori_loop(0, B // rows, body, init)


# ----------------------------------------------------------------------------
# encoder-decoder family (seamless-m4t) — stubbed audio frontend


def _build_encdec_model(cfg: ModelConfig) -> Model:
    def init_params(key):
        k1, k2 = jax.random.split(key)
        params = decoder.init_decoder(k1, cfg)
        params["encoder"] = decoder.init_encoder(k2, cfg)
        return params

    def _encode(params, batch):
        return decoder.encoder_stack(params["encoder"], cfg,
                                     batch["audio_embeds"], remat=cfg.remat)

    def train_loss(params, batch):
        enc = _encode(params, batch)
        x = common.embed(params["embed"], cfg, batch["tokens"])
        x, _ = decoder.decoder_stack(params, cfg, x, mode="train", ctx=enc)
        return chunked_ce_loss(params, cfg, x, batch["labels"])

    def prefill(params, batch):
        enc = _encode(params, batch)
        x = common.embed(params["embed"], cfg, batch["tokens"])
        x, caches = decoder.decoder_stack(params, cfg, x, mode="prefill",
                                          ctx=enc)
        logits = common.unembed(params["embed"], cfg, x[:, -1:, :])
        return logits[:, 0, :], caches

    def decode(params, caches, batch):
        x = common.embed(params["embed"], cfg, batch["token"])
        x, caches = decoder.decoder_stack(params, cfg, x, mode="decode",
                                          caches=caches, pos=batch["pos"],
                                          ctx=None)
        logits = common.unembed(params["embed"], cfg, x)
        return logits[:, 0, :], caches

    def init_cache(batch_size, buf_len, ctx_len=None):
        # ctx_len = encoded source length (== buf_len in the shape cells)
        return decoder.init_decoder_cache(
            cfg, batch_size, buf_len,
            ctx_len=ctx_len if ctx_len is not None else buf_len)

    def input_specs(shape: ShapeCell):
        B, S = shape.global_batch, shape.seq_len
        dt = dtype_of(cfg)
        tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
        audio = jax.ShapeDtypeStruct((B, S, cfg.d_model), dt)
        if shape.kind == "train":
            return {"tokens": tok, "labels": tok, "audio_embeds": audio}
        if shape.kind == "prefill":
            return {"tokens": tok, "audio_embeds": audio}
        return {"token": jax.ShapeDtypeStruct((B, 1), jnp.int32),
                "pos": jax.ShapeDtypeStruct((), jnp.int32)}

    return Model(cfg, init_params, train_loss, prefill, decode, init_cache,
                 input_specs)


def build_model(arch_or_cfg) -> Model:
    cfg = (arch_or_cfg if isinstance(arch_or_cfg, ModelConfig)
           else get_config(arch_or_cfg))
    if cfg.family == "audio":
        return _build_encdec_model(cfg)
    return _build_decoder_model(cfg)
