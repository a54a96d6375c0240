"""Device time of the engine's decode program per step, in ms (the
latent-attention mixture-of-experts engine's cell)."""

from bench.readers import per_call_ms


def read(ctx):
    return per_call_ms(ctx, "decode")
