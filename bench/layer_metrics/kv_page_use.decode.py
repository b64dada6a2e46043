"""Share of the KV pages that the decode steps' attention grid spanned in
the window which held a live request's keys: 100 x
engine.decode_pages_live / engine.decode_pages_spanned, the program's
counters, added per decode segment (slots x the clamped block table's
pages spanned; the pages up to each decoding slot's position live).
Moves ``output_tok_s``: a spanned page that holds no key is grid work
``paged_attention`` does for nothing."""


def read(ctx):
    c = getattr(ctx, "counters", None) or {}
    spanned = c.get("engine.decode_pages_spanned")
    if not spanned:
        return None
    return 100.0 * c.get("engine.decode_pages_live", 0) / spanned
