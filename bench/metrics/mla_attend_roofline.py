"""The decode step's latent attention's share of its roofline, in %: the
least time of the traced steps' attention over the latent cache
(``bench/flops_mla_moe.latent_attend``: the valid latent cache read once,
against the scores and values of every head) over the device time of
the operations in the program's ``mla.latent_attend`` scope of the
decode program."""

from bench import flops_mla_moe as F
from bench.scope_reduce import scope_seconds


def read(ctx):
    if not ctx.trace.program_count("decode"):
        return None
    secs = scope_seconds(ctx, "decode", "mla.latent_attend")
    if not secs:
        return None
    pk = ctx.peak
    least = sum(max(w["flops"] / pk["bf16_flops_per_s"],
                    w["bytes"] / pk["hbm_bytes_per_s"])
                for w in (F.latent_attend(ctx.cfg, b, c)
                          for b, c in F.window_steps(ctx.counters["work"])))
    return 100.0 * least / secs
