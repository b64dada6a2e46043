"""Device time of one decode step: the summed duration of the decode
step's programs (the engine's jitted ``decode_fn``) in the trace, over
their count. Moves ``output_tok_s``."""


def read(ctx):
    if ctx.trace is None:
        return None
    mods = ctx.trace.modules("decode_fn", ctx.window)
    if not mods:
        return None
    return sum(e.end - e.start for e in mods) / len(mods) * 1e-6
