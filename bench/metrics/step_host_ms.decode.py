"""Host time of a decode step, in ms: the ``engine.decode_step`` spans
of the traced window (the decode program's dispatch, the key split and
the step's sampling) over their count.  Near the device's step time the
host sets the pace."""

from bench.span_reduce import of_run

STEP = "engine.decode_step"


def read(ctx):
    red = of_run(ctx)
    if red is None or not red.requests or not red.count(STEP):
        return None
    return 1e3 * red.seconds(STEP) / red.count(STEP)
