"""Device time per request of the engine's prefill program (by blocks of
rows inside the program) and of the copy of its cache into the decode
buffers, in ms (the latent-attention mixture-of-experts engine's cell,
where prefill takes about half of a request).  ``pad_cache`` runs
eagerly: the zeroed buffers are ``broadcast_in_dim`` programs and the
copy ``dynamic_update_slice`` programs of their own."""

PAD_PROGRAMS = ("dynamic_update_slice", "broadcast_in_dim")


def read(ctx):
    t = ctx.trace
    n = t.program_count("prefill")
    if not n:
        return None
    return 1e3 * (t.program_s("prefill") + t.program_s(*PAD_PROGRAMS)) / n
