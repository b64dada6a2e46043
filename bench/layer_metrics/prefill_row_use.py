"""Share of the prefill rows dispatched in the window that carried a real
prompt token: 100 x engine.prefill_tokens / engine.prefill_rows, the
program's counters, added at each prefill dispatch. Moves
``output_tok_s``: a padded row costs the prefill dots as much as a real
one, and every refill's prefill stalls every slot's decode."""


def read(ctx):
    c = getattr(ctx, "counters", None) or {}
    rows = c.get("engine.prefill_rows")
    if not rows:
        return None
    return 100.0 * c.get("engine.prefill_tokens", 0) / rows
