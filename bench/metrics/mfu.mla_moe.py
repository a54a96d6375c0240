"""Model FLOP utilisation of the latent-attention mixture-of-experts
cell, in %: every operation of the requests served in the traced window
(prefills and decode steps, with the routed work the engine's expert
counters report; ``bench/flops_mla_moe.window``) over the window, as a
share of the chip's bf16 peak."""

from bench import flops_mla_moe as F


def read(ctx):
    load = ctx.counters.get("expert_load")
    if not ctx.counters.get("requests") or not load \
            or ctx.trace.window_s <= 0:
        return None
    ops = F.window(ctx.cfg, ctx.counters["work"], load)
    return 100.0 * ops / ctx.trace.window_s / (
        ctx.peak["bf16_flops_per_s"] * ctx.trace.chips)
