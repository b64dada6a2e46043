"""Share of the traced window in which no operation ran on the chip:
1 - busy / window, busy being the union of op intervals in the device
trace. Moves ``output_tok_s`` in the backlog cells, where the device
should never wait for the host."""


def read(ctx):
    if ctx.trace is None or ctx.window_ns <= 0:
        return None
    busy = ctx.trace.busy_ns(ctx.window)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / ctx.window_ns)
