"""Batched inference engine: prefill + decode loop over the Model API.

This is the per-replica execution engine that SynergAI schedules.  One
``InferenceEngine`` corresponds to one deployed "inference engine" in the
paper's terminology: (architecture x serving configuration) on one worker.

Under an active ``jax.profiler`` trace the engine writes host spans on the
profiler's clock, nested on the calling thread: ``engine.generate`` for a
whole request (its ``request`` ordinal, ``rows``, ``prompt_len`` and
``tokens``), and inside it ``engine.prefill``, ``engine.sample`` for each
token, ``engine.decode_step`` for each step after the first token (its
sampling nested in it) and ``engine.wait`` for the final sync, the
stacking of the tokens and, for mixture-of-experts models, the one read
of the cache's expert counters into ``EngineStats``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.models.registry import Model
from repro.serving import sampling
from repro.serving.kvcache import cache_bytes, pad_cache


def _by_phase():
    return {"prefill": 0, "decode": 0}


@dataclasses.dataclass
class EngineStats:
    prefill_tokens: int = 0
    decoded_tokens: int = 0
    batches: int = 0
    # mixture-of-experts models, summed over layers and requests, by
    # phase: routed assignments that landed on the experts the engine
    # holds, and held experts touched (once per layer per program call)
    held_assignments: dict = dataclasses.field(default_factory=_by_phase)
    experts_touched: dict = dataclasses.field(default_factory=_by_phase)

    def add_expert_load(self, loads):
        """``loads``: the cache's ``moe_load`` stacks, on the host."""
        for load in loads:
            for row, phase in enumerate(("prefill", "decode")):
                self.held_assignments[phase] += int(load[:, row, 0].sum())
                self.experts_touched[phase] += int(load[:, row, 1].sum())


class InferenceEngine:
    """Greedy/stochastic batched generation with a persistent KV cache."""

    def __init__(self, model: Model, params, max_len: int = 256,
                 sampler: Callable = sampling.greedy, donate: bool = True):
        self.model = model
        self.params = params
        self.max_len = max_len
        self.sampler = sampler
        self.stats = EngineStats()
        self._prefill = jax.jit(model.prefill)
        # donate the cache buffers across steps
        self._decode = jax.jit(model.decode,
                               donate_argnums=(1,) if donate else ())

    def prefill(self, batch: dict):
        """Run the prompt; returns (last-position logits [B, V], caches
        laid into ``max_len`` decode buffers)."""
        B = batch["tokens"].shape[0]
        with jax.profiler.TraceAnnotation("engine.prefill"):
            logits, caches = self._prefill(self.params, batch)
            ctx_len = (batch["audio_embeds"].shape[1]
                       if "audio_embeds" in batch else None)
            template = self.model.init_cache(B, self.max_len, ctx_len)
            return logits, pad_cache(caches, template)

    def decode_step(self, caches, token, pos: int):
        """One token per row at position ``pos``; ``caches`` is donated.
        Returns (logits [B, V], caches)."""
        return self._decode(self.params, caches,
                            {"token": token[:, None], "pos": jnp.int32(pos)})

    def generate(self, batch: dict, n_tokens: int, key=None):
        """batch: model input_specs-shaped dict with real arrays.

        Returns tokens [B, n_tokens].
        """
        B, prompt_len = batch["tokens"].shape
        self.stats.batches += 1
        with jax.profiler.TraceAnnotation(
                "engine.generate", request=self.stats.batches, rows=B,
                prompt_len=prompt_len, tokens=n_tokens):
            logits, caches = self.prefill(batch)
            self.stats.prefill_tokens += B * prompt_len
            outs = []
            key = key if key is not None else jax.random.PRNGKey(0)
            with jax.profiler.TraceAnnotation("engine.sample", step=0):
                tok = self.sampler(logits, key)
            for i in range(n_tokens):
                outs.append(tok)
                if i == n_tokens - 1:
                    break
                with jax.profiler.TraceAnnotation("engine.decode_step",
                                                  step=i + 1):
                    logits, caches = self.decode_step(caches, tok,
                                                      prompt_len + i)
                    key, sub = jax.random.split(key)
                    with jax.profiler.TraceAnnotation("engine.sample",
                                                      step=i + 1):
                        tok = self.sampler(logits, sub)
            with jax.profiler.TraceAnnotation("engine.wait"):
                jax.block_until_ready(tok)
                out = jnp.stack(outs, axis=1)
                loads = self.model.expert_load(caches)
                if loads:
                    self.stats.add_expert_load(jax.device_get(loads))
            self.stats.decoded_tokens += B * n_tokens
            return out

    def cache_footprint(self, B: int) -> int:
        shapes = jax.eval_shape(lambda: self.model.init_cache(B, self.max_len))
        import numpy as np
        return int(sum(np.prod(l.shape) * l.dtype.itemsize
                       for l in jax.tree.leaves(shapes)))
