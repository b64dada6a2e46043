"""Share of the rows that the routed experts' kernels computed in the
window's decode dispatches which carried a routed entry: 100 x
moe.routed_rows.decode / moe.expert_rows.decode, the program's counters
(real tokens x top_k x MoE layers, against the rows of the dispatch's
static buffers: experts x slots x MoE layers per step). Moves
``output_tok_s``: an empty buffer row is work the expert GEMV does for
nothing."""


def read(ctx):
    c = getattr(ctx, "counters", None) or {}
    rows = c.get("moe.expert_rows.decode")
    if not rows:
        return None
    return 100.0 * c.get("moe.routed_rows.decode", 0) / rows
