"""Task-centric sparse-quantized GEMV — Pallas TPU kernel (paper §3.5).

GPU original: Stream-K work-centric decomposition over CTAs, gathering
surviving INT4 groups and their activation slices. TPU adaptation (see
DESIGN.md §2): the grid is a *1-D flattened work list* of equal-size
(row-block, slot-chunk) items built offline at pack time. Scalar-prefetched
work arrays drive every BlockSpec index map, so each sequential grid step
DMAs exactly one [BN, LANE] tile of BSR payload — equal work per step means
a bubble-free software pipeline, which is the TPU analogue of Stream-K's SM
load balancing.

The TPU has no cross-vreg gather, so the kernel never gathers activations.
Each item instead *places* its dequantized slots at their group columns in
a dense VMEM tile of the row block — a lane gather through the inverse
index ``pos`` (slot of each column), one 128-lane vreg at a time — and the
row block's last item multiplies that tile with the activations on the MXU.
HBM traffic stays the compressed payload; the dense tile exists only in
VMEM, one row block at a time.

Layouts (core/bsr.py, built at pack time):
    x      [T, G*Cp]          activations, group-column-major inside each
                              code position: x[t, j*Cp + c] = x[t, c*G + j]
    words  [G/8, Np, Mp] i32  eight 4-bit codes per word
    scale  [Np, Mp] f32       0 on padding slots
    zero   [Np, Mp] f32
    pos    [Np, Cp] i32       slot of group column c, -1 where pruned
    work   [4, W] i32         (row_block, chunk, first, last) per item
    y      [T, Np] f32
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_T = 256   # activation rows per grid row (prefill is tiled)
VMEM_LIMIT = 64 * 1024 * 1024


def _kernel(work_ref,                                   # scalar prefetch
            words_ref, scale_ref, zero_ref, pos_ref, x_ref,   # VMEM in
            y_ref,                                      # VMEM out
            wd_ref,                                     # scratch
            *, group_size: int, lane: int, cp: int):
    w = pl.program_id(1)

    @pl.when(work_ref[2, w] == 1)
    def _init():
        wd_ref[...] = jnp.zeros_like(wd_ref)

    base = work_ref[1, w] * lane            # first slot of this item's chunk
    scale = scale_ref[...]
    zero = zero_ref[...]
    for j in range(group_size):
        word = words_ref[j // 8]                              # [BN, LANE]
        q = (word >> (4 * (j % 8))) & 0xF
        wj = (q.astype(jnp.float32) - zero) * scale           # [BN, LANE]
        for c0 in range(0, cp, lane):
            rel = pos_ref[:, c0:c0 + lane] - base
            hit = (rel >= 0) & (rel < lane)
            got = jnp.take_along_axis(wj, jnp.clip(rel, 0, lane - 1),
                                      axis=1)
            col = j * cp + c0
            wd_ref[:, col:col + lane] = jnp.where(
                hit, got, wd_ref[:, col:col + lane])

    @pl.when(work_ref[3, w] == 1)
    def _matmul():
        x = x_ref[...]
        y_ref[...] = jax.lax.dot_general(
            x, wd_ref[...].astype(x.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


def gqsa_gemv_pallas(x: jnp.ndarray, words: jnp.ndarray, scale: jnp.ndarray,
                     zero: jnp.ndarray, pos: jnp.ndarray, work: jnp.ndarray,
                     *, group_size: int, block_n: int, lane: int,
                     block_t: int, interpret: bool = False) -> jnp.ndarray:
    """x: [T, G*Cp] in the layout above, T % block_t == 0. Returns
    [T, Np] f32. Items of one row block are consecutive in ``work``, so
    the output tile stays resident in VMEM until its last item writes it.
    """
    t, kp = x.shape
    np_, mp = scale.shape
    cp = pos.shape[1]
    n_words = words.shape[0]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(t // block_t, work.shape[1]),
        in_specs=[
            pl.BlockSpec((n_words, block_n, lane),
                         lambda i, w, wk: (0, wk[0, w], wk[1, w])),
            pl.BlockSpec((block_n, lane),
                         lambda i, w, wk: (wk[0, w], wk[1, w])),
            pl.BlockSpec((block_n, lane),
                         lambda i, w, wk: (wk[0, w], wk[1, w])),
            pl.BlockSpec((block_n, cp), lambda i, w, wk: (wk[0, w], 0)),
            pl.BlockSpec((block_t, kp), lambda i, w, wk: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_t, block_n),
                               lambda i, w, wk: (i, wk[0, w])),
        scratch_shapes=[pltpu.VMEM((block_n, kp), jnp.float32)],
    )
    kernel = functools.partial(_kernel, group_size=group_size, lane=lane,
                               cp=cp)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((t, np_), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="gqsa_gemv",
    )(work, words, scale, zero, pos, x)
