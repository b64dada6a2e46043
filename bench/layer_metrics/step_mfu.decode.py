"""The whole decode step's share of the chip's peak FLOP/s, in %: the
FLOPs the served decode tokens require (2 per kept weight of every
packed linear, attention over each token's real context, the output
head) over the decode programs' device time times the peak. A kernel
taken off the path leaves its roofline silent; this share still bounds
any claimed gain. Moves ``output_tok_s``."""


def read(ctx):
    if ctx.trace is None or not ctx.decode_tokens:
        return None
    ns = ctx.trace.module_ns("decode_fn", ctx.window)
    if not ns:
        return None
    m, comp = ctx.model, ctx.comp
    kept = sum(n * comp.kept(k) * comp.group_size
               for _, _, n, k in m.linears()) * m.layers
    flops = (2.0 * kept * ctx.decode_tokens
             + 4.0 * m.heads * m.head_dim * m.layers
             * ctx.decode_context_tokens
             + 2.0 * m.vocab * m.d * ctx.decode_tokens)
    return 100.0 * flops / (ns * 1e-9 * ctx.peak_flops)
