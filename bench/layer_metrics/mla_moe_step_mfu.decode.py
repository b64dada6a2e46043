"""The whole decode step's share of the chip's peak FLOP/s for an MLA +
MoE model, in %: the FLOPs the served decode tokens require over the
decode programs' device time times the peak. Per token: 2 per kept
weight of every packed linear it runs through (attention, the dense
layers' MLP, the shared experts and its top_k routed experts), 2 per
weight of the absorbed latent up-projections and of the router; latent
attention over each token's real context (heads x 2 x (576 + 512) per
context token per layer); and the output head. A kernel taken off the
path leaves its roofline silent; this share still bounds any claimed
gain. Moves ``output_tok_s``."""


def read(ctx):
    m = ctx.model
    if ctx.trace is None or not ctx.decode_tokens \
            or not hasattr(m, "experts"):
        return None
    ns = ctx.trace.module_ns("decode_fn", ctx.window)
    if not ns:
        return None
    row = m.kv_rank + m.rope
    dense = m.layers * m.heads * m.kv_rank * (m.nope + m.v_dim) \
        + m.moe_layers * m.experts * m.d + m.vocab * m.d
    flops = (2.0 * (m.active_kept(ctx.comp) + dense) * ctx.decode_tokens
             + 2.0 * m.heads * (row + m.kv_rank) * m.layers
             * ctx.decode_context_tokens)
    return 100.0 * flops / (ns * 1e-9 * ctx.peak_flops)
