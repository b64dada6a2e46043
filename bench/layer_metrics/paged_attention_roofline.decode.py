"""``paged_attention`` inside the decode step: least time over measured
kernel time, in %.

Required work per decode token with a context of c tokens, per layer:
the K and V of those c tokens in bfloat16 (2 * kv_heads * head_dim * 2
bytes each) read once, the query in and the output out in bfloat16, and
4 * heads * head_dim * c FLOPs (scores and values). Context lengths are
the real ones of every decode token served in the window, summed.
"""


def work(model, ctx_tokens, tokens):
    hd, h, kh = model.head_dim, model.heads, model.kv_heads
    nbytes = ctx_tokens * 2 * kh * hd * 2 + tokens * 2 * h * hd * 2
    flops = 4.0 * h * hd * ctx_tokens
    return flops * model.layers, nbytes * model.layers


def read(ctx):
    if ctx.trace is None or not ctx.decode_tokens:
        return None
    ns = ctx.trace.kernel_ns("paged_attention", ctx.window,
                             within="decode_fn")
    if not ns:
        return None
    flops, nbytes = work(ctx.model, ctx.decode_context_tokens,
                         ctx.decode_tokens)
    t_f, t_b = flops / ctx.peak_flops, nbytes / ctx.peak_bw
    ctx.note("paged_attention_roofline.decode bound by "
             + ("FLOPs" if t_f > t_b else "bytes")
             + f": {flops:.4g} FLOP, {nbytes:.4g} B in the window")
    return 100.0 * max(t_f, t_b) / (ns * 1e-9)
