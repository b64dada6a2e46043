"""``paged_latent_attention`` (MLA's absorbed attention on the paged
latent pool) inside the decode step: least time over measured kernel
time, in %.

Required work per decode token with a context of c tokens, per layer:
the c latent rows (kv_lora_rank + qk_rope_head_dim wide, 576) in
bfloat16 read once; the absorbed query (heads x 576) in and the latent
context (heads x kv_lora_rank) out in bfloat16; and heads x 2 x (576 +
512) FLOPs per context token (scores over the whole row, values over
its kv_lora_rank lead). Context lengths are the real ones of every
decode token served in the window, summed.
"""


def work(model, ctx_tokens, tokens):
    row = model.kv_rank + model.rope
    nbytes = ctx_tokens * row * 2 \
        + tokens * model.heads * (row + model.kv_rank) * 2
    flops = model.heads * 2.0 * (row + model.kv_rank) * ctx_tokens
    return flops * model.layers, nbytes * model.layers


def read(ctx):
    if ctx.trace is None or not ctx.decode_tokens \
            or not hasattr(ctx.model, "kv_rank"):
        return None
    ns = ctx.trace.kernel_ns("paged_latent_attention", ctx.window,
                             within="decode_fn")
    if not ns:
        return None
    flops, nbytes = work(ctx.model, ctx.decode_context_tokens,
                         ctx.decode_tokens)
    t_f, t_b = flops / ctx.peak_flops, nbytes / ctx.peak_bw
    ctx.note("paged_latent_attention_roofline.decode bound by "
             + ("FLOPs" if t_f > t_b else "bytes")
             + f": {flops:.4g} FLOP, {nbytes:.4g} B in the window")
    return 100.0 * max(t_f, t_b) / (ns * 1e-9)
