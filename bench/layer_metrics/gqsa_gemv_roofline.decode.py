"""``gqsa_gemv`` inside the decode step: least time over measured kernel
time, in %.

Least time is the larger of required FLOPs over the chip's peak FLOP/s
and required bytes over its HBM bandwidth, per decode step. Required
work, whatever implements it:

* weights in the paper's deployed BSR format, read once per step: per
  kept group of G codes, G/2 bytes of 4-bit codes, an fp16 scale, a u8
  zero and an int16 group column; plus a 4-byte row offset per row
  (the arithmetic of ``paper_bsr_nbytes`` in the program's core/bsr.py);
* activations in and out in bfloat16 for the real tokens of the step;
* 2 FLOPs per kept weight per real token (padded rows not counted).
"""


def work(model, comp, tokens):
    """(flops, bytes) of every packed linear of one step over ``tokens``
    real rows."""
    g = comp.group_size
    flops = nbytes = 0.0
    for _, _, n, k in model.linears():
        m = comp.kept(k)
        nbytes += 4 * (n + 1) + n * m * (g * comp.bits // 8 + 2 + 2 + 1)
        nbytes += tokens * (n + k) * 2
        flops += 2.0 * n * m * g * tokens
    return flops * model.layers, nbytes * model.layers


def read(ctx):
    if ctx.trace is None:
        return None
    steps = len(ctx.trace.modules("decode_fn", ctx.window))
    ns = ctx.trace.kernel_ns("gqsa_gemv", ctx.window, within="decode_fn")
    if not steps or not ns:
        return None
    flops, nbytes = work(ctx.model, ctx.comp, ctx.decode_tokens / steps)
    t_f, t_b = flops / ctx.peak_flops, nbytes / ctx.peak_bw
    ctx.note("gqsa_gemv_roofline.decode bound by "
             + ("FLOPs" if t_f > t_b else "bytes")
             + f": {flops:.4g} FLOP, {nbytes:.4g} B per step")
    return 100.0 * steps * max(t_f, t_b) / (ns * 1e-9)
