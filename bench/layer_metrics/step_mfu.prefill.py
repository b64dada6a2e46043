"""The prefill programs' share of the chip's peak FLOP/s in the window,
in %: the FLOPs the refills' real prompts require (2 per kept weight per
prompt token, causal attention over the prompt, the output head at each
prompt's last position only) over the prefill programs' device time
times the peak. Padded rows are work the step did but the prompts did
not need. Moves ``output_tok_s``."""


def read(ctx):
    if ctx.trace is None:
        return None
    ns = ctx.trace.module_ns("prefill_fn", ctx.window)
    lens = [r.prompt_len for r in ctx.requests
            if r.admitted and not r.first_fill]
    if not ns or not lens:
        return None
    m, comp = ctx.model, ctx.comp
    kept = sum(n * comp.kept(k) * comp.group_size
               for _, _, n, k in m.linears()) * m.layers
    flops = sum(2.0 * kept * p
                + 4.0 * m.heads * m.head_dim * m.layers * p * (p + 1) / 2
                + 2.0 * m.vocab * m.d for p in lens)
    return 100.0 * flops / (ns * 1e-9 * ctx.peak_flops)
