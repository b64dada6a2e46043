"""Device time of the prefill programs (the engine's jitted
``prefill_fn``) in the window per thousand real prompt tokens that the
window prefilled (the refills; the first fill is prefilled before the
window opens). Padding rows cost time but are not counted as tokens,
so padded prefill reads as slow. Moves ``output_tok_s``: in a backlog,
every refill's prefill stalls every slot's decode."""


def read(ctx):
    if ctx.trace is None:
        return None
    tokens = sum(r.prompt_len for r in ctx.requests
                 if r.admitted and not r.first_fill)
    ns = ctx.trace.module_ns("prefill_fn", ctx.window)
    if not tokens or not ns:
        return None
    return ns * 1e-6 / (tokens / 1000.0)
