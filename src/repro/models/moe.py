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
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.gqs_layer import apply_linear
from repro.models.layers import linear_init, mlp_block, mlp_init


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
    """x: [T, d] -> (gates [T, K], expert ids [T, K], aux loss scalar)."""
    logits = apply_linear(router_p, x.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                # [T, E]
    top_vals, top_idx = jax.lax.top_k(probs, moe.top_k)
    gates = top_vals / jnp.maximum(
        jnp.sum(top_vals, axis=-1, keepdims=True), 1e-9)
    # load-balancing aux: E * sum_e f_e * P_e
    e = moe.n_experts
    assign = jnp.zeros((x.shape[0], e), jnp.float32)
    assign = assign.at[jnp.arange(x.shape[0])[:, None], top_idx].set(1.0)
    f = jnp.mean(assign, axis=0)
    pbar = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(f * pbar)
    return gates, top_idx, aux


def _expert_ffn(experts: Dict, x_buf: jnp.ndarray,
                use_pallas: bool = False, fsdp_axes=None) -> jnp.ndarray:
    """x_buf: [E_loc, C, d] -> [E_loc, C, d] via per-expert SwiGLU.

    When the expert weights are FSDP-sharded on d_ff (dist/sharding.py),
    each shard computes its d_ff slice and the wd contraction is a partial
    product psum'd over the FSDP axes — cheap activation traffic instead of
    weight all-gathers.
    """
    # NOTE: fsdp_axes is unused — with tokens data-sharded, FSDP'd expert
    # weights MUST be gathered per use (a d_ff-partial psum across data
    # shards would mix different tokens' partials). Kept in the signature to
    # document the rejected §Perf hypothesis.
    def one(pe, xe):
        g = apply_linear(pe["wg"], xe, use_pallas=use_pallas)
        u = apply_linear(pe["wu"], xe, use_pallas=use_pallas)
        return apply_linear(pe["wd"], jax.nn.silu(g) * u,
                            use_pallas=use_pallas)
    return jax.vmap(one)(experts, x_buf)


def _dispatch_compute(x: jnp.ndarray, gates: jnp.ndarray,
                      top_idx: jnp.ndarray, experts: Dict,
                      e_offset, e_local: int, capacity: int,
                      use_pallas: bool = False,
                      fsdp_axes=None) -> jnp.ndarray:
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

    y_buf = _expert_ffn(experts, x_buf, use_pallas,
                        fsdp_axes=fsdp_axes)                 # [E_loc, C, d]

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

        fsdp_ax = dist.fsdp_axis if dist.fsdp else None

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
        y = y + mlp_block(p["shared"], xf, "swiglu", use_pallas)
    return y.reshape(b, s, d), aux
