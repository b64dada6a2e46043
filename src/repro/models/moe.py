"""Mixture-of-Experts block (DeepSeek-style: shared + routed top-k).

Expert parallelism: expert-stacked weights are sharded on the `model` axis.
The block runs under shard_map — every model shard routes the (replicated)
tokens, dispatches the entries belonging to its *local* experts into a
contiguous [E_loc, capacity, d] buffer with a scatter (local, so no GSPMD
scatter hazards), runs the expert FFNs as batched matmuls, and the partial
outputs are psum-combined across the model axis. This is the
"replicated-dispatch + psum-combine" EP scheme; the all-to-all variant is a
§Perf iteration (see EXPERIMENTS.md).

The same dispatch code runs single-device (e_offset=0, E_loc=E) so smoke
tests and the distributed path share one implementation.

Serving (:func:`moe_serve`, the engine's prefill and decode steps) drops
no routed entry, whatever the routing, so a token's output never depends
on what else is in its batch. Up to DENSE_DISPATCH_ROWS rows (decode) a
buffer of every row per expert holds all entries and the expert linears
run as one fused GEMV over the expert stack (``gqsa_gemv_ew*``). Above it
(prefill) the entries are sorted by expert and a grouped matmul runs them
against the expert weights, densified once per call
(``gqsa_densify_ew*``), so the work follows the routed rows, not E x T.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.bsr import to_dense
from repro.core.gqs_layer import apply_linear
from repro.kernels import ops as kops
from repro.models.layers import linear_init, mlp_init

# rows of a serving dispatch up to which every expert gets a buffer of
# all of them: one activation tile of the fused GEMV
DENSE_DISPATCH_ROWS = kops.DEFAULT_GEMV_BLOCK_T
EXPERT_LABELS = {"wg": "ewg", "wu": "ewu", "wd": "ewd"}


def moe_init(rng, cfg, dtype=jnp.float32) -> Dict:
    moe = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(rng, 5)
    scale = 1.0 / jnp.sqrt(d)

    def expert_stack(key, n_out, n_in):
        w = jax.random.normal(key, (moe.n_experts, n_out, n_in), dtype) * scale
        return {"w": w}

    p = {
        "router": linear_init(ks[0], moe.n_experts, d, dtype),
        "experts": {
            "wg": expert_stack(ks[1], moe.d_expert, d),
            "wu": expert_stack(ks[2], moe.d_expert, d),
            "wd": expert_stack(ks[3], d, moe.d_expert),
        },
    }
    if moe.n_shared:
        # shared experts fused into one wide SwiGLU (block-diagonal equiv.)
        p["shared"] = mlp_init(ks[4], d, moe.n_shared * moe.d_expert,
                               "swiglu", dtype)
    return p


def _route(router_p: Dict, x: jnp.ndarray, moe) -> Tuple[jnp.ndarray,
                                                         jnp.ndarray,
                                                         jnp.ndarray]:
    """x: [T, d] -> (gates [T, K], expert ids [T, K], aux loss scalar).
    The router is dense (core/model_compress.py keeps it FP) and runs in
    f32 at full precision, as does the softmax."""
    x = x.astype(jnp.float32)
    logits = jnp.einsum("td,ed->te", x, router_p["w"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)                # [T, E]
    top_vals, top_idx = jax.lax.top_k(probs, moe.top_k)
    if moe.norm_topk_prob:
        gates = top_vals / jnp.maximum(
            jnp.sum(top_vals, axis=-1, keepdims=True), 1e-9)
    else:
        gates = top_vals * moe.routed_scaling_factor
    # load-balancing aux: E * sum_e f_e * P_e
    e = moe.n_experts
    assign = jnp.zeros((x.shape[0], e), jnp.float32)
    assign = assign.at[jnp.arange(x.shape[0])[:, None], top_idx].set(1.0)
    f = jnp.mean(assign, axis=0)
    pbar = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * pbar)
    return gates, top_idx, aux


def _expert_ffn(experts: Dict, x_buf: jnp.ndarray,
                use_pallas: bool = False) -> jnp.ndarray:
    """x_buf: [E_loc, C, d] -> [E_loc, C, d] via per-expert SwiGLU. Packed
    experts with buffers of one activation tile run one fused GEMV per
    linear over the whole stack."""
    def lin(name, xb):
        w = experts[name]
        if use_pallas and "bsr" in w and \
                xb.shape[1] <= DENSE_DISPATCH_ROWS:
            return kops.gqsa_gemv_experts(xb, w["bsr"], EXPERT_LABELS[name]
                                          ).astype(xb.dtype)
        return jax.vmap(lambda pe, xe: apply_linear(
            pe, xe, use_pallas=use_pallas,
            label=EXPERT_LABELS[name]))(w, xb)
    g = lin("wg", x_buf)
    u = lin("wu", x_buf)
    return lin("wd", jax.nn.silu(g) * u)


def _dispatch_compute(x: jnp.ndarray, gates: jnp.ndarray,
                      top_idx: jnp.ndarray, experts: Dict,
                      e_offset, e_local: int, capacity: int,
                      use_pallas: bool = False) -> jnp.ndarray:
    """Scatter entries for local experts into buffers, compute, gather back.

    x: [T, d]; gates/top_idx: [T, K]. Returns partial y [T, d] covering only
    the local experts' contributions.
    """
    t, d = x.shape
    k = top_idx.shape[1]
    flat_eid = top_idx.reshape(-1)                         # [T*K] global ids
    lid = flat_eid - e_offset
    is_local = (lid >= 0) & (lid < e_local)
    lid_safe = jnp.where(is_local, lid, 0)

    # position of each entry within its expert's buffer
    onehot = (lid_safe[:, None] == jnp.arange(e_local)[None, :]) & \
        is_local[:, None]                                   # [T*K, E_loc]
    oh = onehot.astype(jnp.int32)
    pos = jnp.cumsum(oh, axis=0) - oh
    entry_pos = jnp.sum(pos * oh, axis=-1)                  # [T*K]
    keep = is_local & (entry_pos < capacity)
    entry_pos = jnp.where(keep, entry_pos, capacity - 1)

    token_id = jnp.arange(t * k) // k
    x_flat = x[token_id]                                    # [T*K, d]
    x_buf = jnp.zeros((e_local, capacity, d), x.dtype)
    x_buf = x_buf.at[lid_safe, entry_pos].add(
        jnp.where(keep[:, None], x_flat, 0))

    y_buf = _expert_ffn(experts, x_buf, use_pallas)         # [E_loc, C, d]

    y_flat = y_buf[lid_safe, entry_pos]                     # [T*K, d]
    y_flat = jnp.where(keep[:, None], y_flat, 0)
    gates_flat = gates.reshape(-1, 1).astype(y_flat.dtype)
    y = jnp.sum((y_flat * gates_flat).reshape(t, k, d), axis=1)
    return y


def moe_block(p: Dict, x: jnp.ndarray, cfg, dist=None,
              use_pallas: bool = False) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: [B, S, d] -> (y [B, S, d], aux loss). EP over `model` when dist."""
    moe = cfg.moe
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    capacity = max(1, int(t * moe.top_k / moe.n_experts
                          * moe.capacity_factor))

    ep = (dist is not None and dist.mesh is not None
          and moe.n_experts % dist.axis_size(dist.model_axis) == 0)

    if not ep:
        gates, top_idx, aux = _route(p["router"], xf, moe)
        y = _dispatch_compute(xf, gates, top_idx, p["experts"], 0,
                              moe.n_experts, capacity, use_pallas)
    else:
        n_shards = dist.axis_size(dist.model_axis)
        e_local = moe.n_experts // n_shards
        maxis = dist.model_axis
        dp = dist.batch_axes

        def local(xl, router_p, experts_l):
            tl = xl.shape[0]
            cap_l = max(1, int(tl * moe.top_k / moe.n_experts
                               * moe.capacity_factor))
            gates, top_idx, aux_l = _route(router_p, xl, moe)
            e_off = jax.lax.axis_index(maxis) * e_local
            yl = _dispatch_compute(xl, gates, top_idx, experts_l, e_off,
                                   e_local, cap_l, use_pallas)
            yl = jax.lax.psum(yl, maxis)
            aux_l = jax.lax.pmean(aux_l, dp) if dp else aux_l
            return yl, aux_l

        # expert weights arrive GATHERED over the FSDP axis (ZeRO-3
        # semantics: shard for storage, gather for compute)
        expert_specs = jax.tree_util.tree_map(
            lambda l: P(maxis, *([None] * (l.ndim - 1))), p["experts"])
        router_specs = jax.tree_util.tree_map(
            lambda l: P(*([None] * l.ndim)), p["router"])
        y, aux = shard_map(
            local, mesh=dist.mesh,
            in_specs=(P(dp, None), router_specs, expert_specs),
            out_specs=(P(dp, None), P()),
            check_vma=False,
        )(xf.reshape(t, d), p["router"], p["experts"])

    if "shared" in p:
        y = y + _shared(p["shared"], xf, use_pallas)
    return y.reshape(b, s, d), aux


def _shared(p: Dict, x: jnp.ndarray, use_pallas: bool) -> jnp.ndarray:
    """The shared experts, fused into one wide SwiGLU."""
    def lin(name, xx):
        return apply_linear(p[name], xx, use_pallas=use_pallas,
                            label="s" + name)
    return lin("wd", jax.nn.silu(lin("wg", x)) * lin("wu", x))


def expert_rows(cfg, rows: int) -> int:
    """Rows each expert linear of one MoE layer computes when
    :func:`moe_serve` dispatches ``rows`` token rows: a buffer of every
    row per expert up to DENSE_DISPATCH_ROWS, else one grouped row per
    routed entry. From static shapes only, for the engine's counters."""
    moe = cfg.moe
    if rows <= DENSE_DISPATCH_ROWS:
        return moe.n_experts * rows
    return rows * moe.top_k


def moe_serve(p: Dict, x: jnp.ndarray, cfg, use_pallas: bool = False,
              valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """The serving MoE block: x [B, S, d] -> y [B, S, d], dropping no
    routed entry. ``valid`` [B, S] marks the rows that carry tokens;
    padding rows are left unrouted above DENSE_DISPATCH_ROWS (their
    output is the shared experts' alone)."""
    moe = cfg.moe
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    gates, top_idx, _ = _route(p["router"], xf, moe)
    if t <= DENSE_DISPATCH_ROWS:
        y = _dispatch_compute(xf, gates, top_idx, p["experts"], 0,
                              moe.n_experts, t, use_pallas)
    else:
        keep = None if valid is None else valid.reshape(t)
        y = _grouped(p["experts"], xf, gates, top_idx, keep, moe.n_experts,
                     use_pallas)
    if "shared" in p:
        y = y + _shared(p["shared"], xf, use_pallas)
    return y.reshape(b, s, d)


def _grouped(experts: Dict, x: jnp.ndarray, gates: jnp.ndarray,
             top_idx: jnp.ndarray, keep: Optional[jnp.ndarray], e: int,
             use_pallas: bool) -> jnp.ndarray:
    """Every routed entry (one per token and chosen expert), sorted by
    expert, through each expert's SwiGLU as one grouped matmul per
    linear; entries of rows not kept sort last and join no group."""
    t, d = x.shape
    k = top_idx.shape[1]
    eid = top_idx.reshape(-1)
    if keep is not None:
        eid = jnp.where(jnp.repeat(keep, k), eid, e)
    order = jnp.argsort(eid, stable=True)
    sizes = jnp.bincount(eid, length=e + 1)[:e].astype(jnp.int32)
    xs = x[order // k]

    def lin(name, rows):
        return _grouped_linear(experts[name], rows, sizes, use_pallas,
                               EXPERT_LABELS[name])
    ys = lin("wd", jax.nn.silu(lin("wg", xs)) * lin("wu", xs))
    routed = eid < e
    y_flat = jnp.zeros_like(ys).at[order].set(ys)          # entry order
    w = jnp.where(routed, gates.reshape(-1), 0).astype(ys.dtype)
    return jnp.sum((y_flat * w[:, None]).reshape(t, k, d), axis=1)


def _grouped_linear(w: Dict, xs: jnp.ndarray, sizes: jnp.ndarray,
                    use_pallas: bool, label: str) -> jnp.ndarray:
    """xs [M, K] sorted by expert times each expert's weight of the stack
    ``w`` -> [M, N] in xs's dtype; rows past sum(sizes) are zero."""
    if "bsr" in w:
        bsr = w["bsr"]
        n = bsr.shape[0]
        if use_pallas:
            wd = kops.gqsa_densify_experts(bsr, xs.dtype, label)
            y = kops.grouped_matmul(kops.group_major(xs, bsr.group_size),
                                    wd, sizes)
            return y[:, :n].astype(xs.dtype)
        dense = to_dense(bsr, xs.dtype)
    else:
        dense = w["w"].astype(xs.dtype)
    dn = jax.lax.RaggedDotDimensionNumbers(
        dot_dimension_numbers=(([1], [2]), ([], [])),
        lhs_ragged_dimensions=[0], rhs_group_dimensions=[0])
    return jax.lax.ragged_dot_general(
        xs, dense, sizes, dn, preferred_element_type=jnp.float32
    ).astype(xs.dtype)
