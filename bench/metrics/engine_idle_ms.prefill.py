"""Device idle time between programs while the host was inside the
engine (an ``engine.*`` span, ``serving/engine.py``) per request, in
ms."""

from bench.span_reduce import Spans, per_request_ms


def read(ctx):
    return per_request_ms(ctx, Spans.engine_idle_s)
