"""The decode step's routed experts' share of their roofline, in %: the
least time of the traced steps' held experts (the weights of the held
experts the engine's counters say the steps touched, against the routed
rows they computed; ``bench/flops_mla_moe``) over the device time of the
operations in the program's ``moe.experts`` scope of the decode
program."""

from bench import flops_mla_moe as F
from bench.scope_reduce import scope_seconds


def read(ctx):
    load = ctx.counters.get("expert_load")
    if not ctx.trace.program_count("decode") or not load:
        return None
    secs = scope_seconds(ctx, "decode", "moe.experts")
    if not secs:
        return None
    pk = ctx.peak
    least = max(F.expert_flops(ctx.cfg, load["held_assignments"]["decode"])
                / pk["bf16_flops_per_s"],
                F.expert_bytes(ctx.cfg, load["experts_touched"]["decode"])
                / pk["hbm_bytes_per_s"])
    return 100.0 * least / secs
