"""Share of the traced window in which no operation ran on the chip,
in %."""

from bench.readers import idle_pct


def read(ctx):
    return idle_pct(ctx)
