"""Share of the decode step's slots that carried a live request: decode
tokens over (decode steps x slots), from the engine's counters. Moves
``output_tok_s``: an empty slot is a token the step could have made."""


def read(ctx):
    if not ctx.decode_steps:
        return None
    return 100.0 * ctx.decode_tokens / (ctx.decode_steps * ctx.slots)
