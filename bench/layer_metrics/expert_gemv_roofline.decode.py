"""The routed experts' ``gqsa_gemv`` (``gqsa_gemv_ewg`` / ``_ewu`` /
``_ewd``) inside the decode step: least time over measured kernel time,
in %.

Least time per decode step is the larger of required FLOPs over the
chip's peak FLOP/s and required bytes over its HBM bandwidth:

* every held expert's weights in the paper's deployed BSR format (per
  kept group of G codes, G/2 bytes of 4-bit codes, an fp16 scale, a u8
  zero and an int16 group column; a 4-byte row offset per row), read
  once per step. Counting all E experts of every MoE layer over-counts
  the bytes a step needs by at most E / E(1 - (1 - 1/E)^(T k)): with 32
  tokens x top-6 = 192 entries over 64 experts, 61.2 experts are hit on
  average, so at most 64 / 61.2;
* the routed rows' activations in and out in bfloat16 (tokens x top_k
  rows per linear);
* 2 FLOPs per kept weight per routed row; the dispatch's empty buffer
  rows are not counted.
"""


def work(model, comp, tokens):
    """(flops, bytes) of the routed experts of one decode step over
    ``tokens`` real tokens."""
    g = comp.group_size
    rows = tokens * model.top_k
    flops = nbytes = 0.0
    for _, n, k in model.swiglu(model.d_expert, model.d):
        m = comp.kept(k)
        nbytes += model.experts * (4 * (n + 1)
                                   + n * m * (g * comp.bits // 8 + 5))
        nbytes += rows * (n + k) * 2
        flops += 2.0 * n * m * g * rows
    return flops * model.moe_layers, nbytes * model.moe_layers


def read(ctx):
    if ctx.trace is None or not hasattr(ctx.model, "experts"):
        return None
    steps = len(ctx.trace.modules("decode_fn", ctx.window))
    ns = ctx.trace.kernel_ns("gqsa_gemv_ew", ctx.window, within="decode_fn")
    if not steps or not ns:
        return None
    flops, nbytes = work(ctx.model, ctx.comp, ctx.decode_tokens / steps)
    t_f, t_b = flops / ctx.peak_flops, nbytes / ctx.peak_bw
    ctx.note("expert_gemv_roofline.decode bound by "
             + ("FLOPs" if t_f > t_b else "bytes")
             + f": {flops:.4g} FLOP, {nbytes:.4g} B per step")
    return 100.0 * steps * max(t_f, t_b) / (ns * 1e-9)
