"""The decode step's share of its roofline, in %: the least time of the
traced steps (``bench/flops_mla_moe.decode_step``: the weights every step
reads, the held experts the engine's counters say were touched and the
valid latent cache, against the step's operations) over their measured
device time.  The window's expert counters are spread evenly over its
steps."""

from bench import flops_mla_moe as F


def read(ctx):
    t = ctx.trace
    n = t.program_count("decode")
    load = ctx.counters.get("expert_load")
    steps = F.window_steps(ctx.counters["work"])
    if not n or not load or len(steps) != n:
        return None
    a = load["held_assignments"]["decode"] / n
    touched = load["experts_touched"]["decode"] / n
    pk = ctx.peak
    least = sum(max(w["flops"] / pk["bf16_flops_per_s"],
                    w["bytes"] / pk["hbm_bytes_per_s"])
                for w in (F.decode_step(ctx.cfg, b, c, a, touched)
                          for b, c in steps))
    return 100.0 * least / t.program_s("decode")
