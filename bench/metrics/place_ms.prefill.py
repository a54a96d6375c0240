"""Host time of placement per request, in ms: the ``placement.estimate``
spans (``core/estimator.estimate_matrix``) of the traced window over its
requests (``engine.generate`` spans)."""

from bench.span_reduce import per_request_ms


def read(ctx):
    return per_request_ms(ctx, lambda red: red.seconds("placement.estimate"))
