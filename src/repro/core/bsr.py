"""Block-Sparse-Row storage for GQS layers (paper §3.2 + Figure 3).

Canonical paper form (exact, ragged):
    rowIndex[N+1]  -- prefix offsets; rowIndex[i+1]-rowIndex[i] = groups in row i
    groups[nnz]    -- column index (in group units) of each surviving group
    values[nnz, G] -- INT4 codes (packed two-per-byte -> [nnz, G/2] uint8)
    scale/zero[nnz]

TPU padded tensor form (what the models & kernels consume), built once at
pack time in the layout the GEMV kernel reads (kernels/gqsa_gemv.py):
    words [G/8, Np, Mp] int32 -- code j of slot (n, m) is nibble j % 8 of
                                 words[j // 8, n, m]; padding slots are 0
    scale [Np, Mp] f32        -- 0 on padding (=> dequant contributes nothing)
    zero  [Np, Mp] f32
    pos   [Np, Cp] int32      -- slot m holding group column c of row n,
                                 -1 where the group was pruned
    work  [4, W] int32        -- task-centric work list (row_block, chunk,
                                 first, last), see :func:`build_work_list`
M = max groups kept per row (== exact count in row_balanced mode); slots
are sorted by column. Np, Mp and Cp = K/G pad N, M and the group columns
to the kernel's tiles (``block_n`` rows, ``lane`` slots / columns). Every
leaf may carry leading stack dims ([L, ...] for scanned layers).

Compression accounting matches the paper: positions stored per *group*, not
per element, so metadata amortizes over G elements.
"""
from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.quant import (QuantConfig, group_minmax_params, quantize,
                              pack_int4)

BLOCK_N = 128   # output rows per kernel tile (sublane dim of its operands)
LANE = 128      # slots per work item == group columns per gather (lanes)


@dataclasses.dataclass
class BSRMatrix:
    """Padded kernel-layout form (a pytree; see the module docstring)."""
    words: jnp.ndarray      # [G/8, Np, Mp] int32
    scale: jnp.ndarray      # [Np, Mp] float32
    zero: jnp.ndarray       # [Np, Mp] float32
    pos: jnp.ndarray        # [Np, Cp] int32 (-1 = pruned group)
    work: jnp.ndarray       # [4, W] int32
    shape: Tuple[int, int]  # dense (N, K)
    group_size: int
    bits: int = 4
    m: int = 0              # kept groups per row (max over rows)
    block_n: int = BLOCK_N
    lane: int = LANE

    def tree_flatten(self):
        return ((self.words, self.scale, self.zero, self.pos, self.work),
                (self.shape, self.group_size, self.bits, self.m,
                 self.block_n, self.lane))

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        return cls(*leaves, *aux)

    @property
    def n_rows(self) -> int:
        return self.shape[0]

    @property
    def n_cols(self) -> int:
        return self.shape[1]

    @property
    def m_groups(self) -> int:
        return self.m

    @property
    def idx(self) -> np.ndarray:
        """Canonical [..., N, M] kept group columns (sorted; -1 padding),
        recovered from ``pos`` on the host (offline tools and tests)."""
        pos = np.asarray(self.pos)
        lead = pos.shape[:-2]
        flat = pos.reshape((-1,) + pos.shape[-2:])
        out = np.full((flat.shape[0], flat.shape[1], self.m + 1), -1,
                      np.int32)
        li, ni, ci = np.nonzero(flat >= 0)
        out[li, ni, flat[li, ni, ci]] = ci
        return out[:, :self.shape[0], :self.m].reshape(
            lead + (self.shape[0], self.m))

    def nbytes_packed(self) -> int:
        """Actual storage bytes of the compressed representation
        (we count what we store, padding included)."""
        return int(sum(l.nbytes for l in self.tree_flatten()[0]))

    def dense_nbytes_fp16(self) -> int:
        return 2 * self.shape[0] * self.shape[1]

    def compression_ratio(self) -> float:
        return self.dense_nbytes_fp16() / self.nbytes_packed()


jax.tree_util.register_pytree_node(
    BSRMatrix, BSRMatrix.tree_flatten, BSRMatrix.tree_unflatten)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def tiles(n: int, k: int, group_size: int, m: int):
    """Kernel tiling of an (n, k) weight keeping m groups per row:
    (block_n, lane, Np, Mp, Cp). Lanes are 128 wide; a weight with at
    most 128 group columns uses its whole column count as one lane tile
    (a full-extent block), so small layers are not padded to 128."""
    c = k // group_size
    lane = c if c <= LANE else LANE
    block_n = min(BLOCK_N, _round_up(n, 8))
    return (block_n, lane, _round_up(n, block_n), _round_up(m, lane),
            _round_up(c, lane))


def codes(words: jnp.ndarray) -> jnp.ndarray:
    """[..., G/8, Np, Mp] int32 words -> [..., Np, Mp, G] int32 codes."""
    w = jnp.moveaxis(jnp.asarray(words), -3, -1)[..., None]   # [.., G/8, 1]
    c = (w >> (4 * jnp.arange(8, dtype=jnp.int32))) & 0xF      # [.., G/8, 8]
    return c.reshape(c.shape[:-2] + (c.shape[-2] * 8,))


def _layout(idx: np.ndarray, q: np.ndarray, scale: np.ndarray,
            zero: np.ndarray, k: int, group_size: int,
            bits: int) -> BSRMatrix:
    """Canonical (idx [N, M], codes [N, M, G], scale/zero [N, M]) -> the
    kernel layout, padded and with its work list. Host-side, pack time."""
    n, m = idx.shape
    g = group_size
    if g % 8:
        raise ValueError(f"group_size {g} must be a multiple of 8 (codes "
                         "pack eight nibbles per int32 word)")
    bn, ln, np_, mp, cp = tiles(n, k, g, m)
    qp = np.zeros((np_, mp, g), np.uint32)
    qp[:n, :m] = q
    shifts = (4 * np.arange(8, dtype=np.uint32))
    words = (qp.reshape(np_, mp, g // 8, 8) << shifts).sum(
        axis=-1, dtype=np.uint32)
    words = np.moveaxis(words, -1, 0).view(np.int32)          # [G/8, Np, Mp]
    sp = np.zeros((np_, mp), np.float32)
    zp = np.zeros((np_, mp), np.float32)
    sp[:n, :m] = scale
    zp[:n, :m] = zero
    pos = np.full((np_, cp), -1, np.int32)
    rows, slots = np.nonzero(idx >= 0)
    pos[rows, idx[rows, slots]] = slots
    idx_p = np.full((np_, mp), -1, np.int32)
    idx_p[:n, :m] = idx
    wl = build_work_list(idx_p, bn, ln)
    work = np.stack([wl.row_block, wl.chunk, wl.first, wl.last])
    return BSRMatrix(words=jnp.asarray(words), scale=jnp.asarray(sp),
                     zero=jnp.asarray(zp), pos=jnp.asarray(pos),
                     work=jnp.asarray(work), shape=(n, k), group_size=g,
                     bits=bits, m=m, block_n=bn, lane=ln)


def _kept_columns(gm: np.ndarray) -> np.ndarray:
    """Group mask [N, K/G] -> sorted kept columns [N, M] (-1 padding)."""
    n = gm.shape[0]
    counts = gm.sum(axis=1)
    m = max(int(counts.max()) if counts.size else 0, 1)
    if counts.size and counts.max() and counts.min() == counts.max():
        # row-balanced fast path: nonzero() is row-major => already sorted
        return np.nonzero(gm)[1].reshape(n, m).astype(np.int32)
    idx = np.full((n, m), -1, dtype=np.int32)
    for i in range(n):
        cols = np.nonzero(gm[i])[0]
        idx[i, :cols.shape[0]] = cols
    return idx


def pack_dense(w: jnp.ndarray, gmask: jnp.ndarray,
               qcfg: QuantConfig) -> BSRMatrix:
    """Dense W [N, K] + group mask [N, K/G] -> padded BSR with per-group
    INT4 quantization of the surviving groups."""
    n, k = w.shape
    g = qcfg.group_size
    idx = _kept_columns(np.asarray(gmask))
    m = idx.shape[1]
    # Gather surviving groups: [N, M, G] (padding slots gather group 0 and
    # are zeroed below).
    wg = jnp.asarray(w).reshape(n, k // g, g)
    taken = jnp.take_along_axis(wg, jnp.maximum(idx, 0)[..., None], axis=1)
    qc = QuantConfig(bits=qcfg.bits, group_size=g)
    scale, zero = group_minmax_params(taken.reshape(n, m * g), qc)
    q = quantize(taken.reshape(n, m * g), scale, zero, qc).reshape(n, m, g)
    pad = idx < 0
    return _layout(idx, np.where(pad[..., None], 0, np.asarray(q)),
                   np.where(pad, 0.0, np.asarray(scale)),
                   np.where(pad, 0.0, np.asarray(zero)), k, g, qcfg.bits)


def pack_quantized(q_codes: jnp.ndarray, gmask: jnp.ndarray,
                   scale: jnp.ndarray, zero: jnp.ndarray,
                   group_size: int, bits: int = 4) -> BSRMatrix:
    """Pack *already-quantized* codes with their (trained) scale/zero —
    the E2E-OQP output path, preserving the fine-tuned quant params exactly.

    q_codes: [N, K] uint8; gmask/scale/zero: [N, K/G].
    """
    n, k = q_codes.shape
    g = group_size
    idx = _kept_columns(np.asarray(gmask))
    safe = np.maximum(idx, 0)
    pad = idx < 0
    qg = np.asarray(q_codes).reshape(n, k // g, g)
    taken = np.take_along_axis(qg, safe[..., None], axis=1)
    sc = np.take_along_axis(np.asarray(scale, np.float32), safe, axis=1)
    zc = np.take_along_axis(np.asarray(zero, np.float32), safe, axis=1)
    return _layout(idx, np.where(pad[..., None], 0, taken),
                   np.where(pad, 0.0, sc), np.where(pad, 0.0, zc), k, g,
                   bits)


def stack_bsr(mats: List[BSRMatrix], lead: Tuple[int, ...]) -> BSRMatrix:
    """Stack per-slice packs (one per scanned layer / expert) into one
    BSRMatrix with leading dims ``lead``. The scan hands every layer a
    work list of the same length, so shorter lists (ragged rows) are
    padded with no-op items: repeats of their final item with both flags
    clear, which re-gather idempotently and never write the output."""
    w_max = max(int(b.work.shape[-1]) for b in mats)
    mp = max(int(b.scale.shape[-1]) for b in mats)

    def work(b):
        wk = np.asarray(b.work)
        pad = np.repeat(wk[:, -1:], w_max - wk.shape[1], axis=1)
        pad[2:] = 0
        return np.concatenate([wk, pad], axis=1)

    def slots(a, fill=0):
        widths = [(0, 0)] * (a.ndim - 1) + [(0, mp - a.shape[-1])]
        return np.pad(np.asarray(a), widths, constant_values=fill)

    def stk(xs):
        return jnp.asarray(np.stack(xs).reshape(lead + xs[0].shape))

    b0 = mats[0]
    return BSRMatrix(words=stk([slots(b.words) for b in mats]),
                     scale=stk([slots(b.scale) for b in mats]),
                     zero=stk([slots(b.zero) for b in mats]),
                     pos=stk([np.asarray(b.pos) for b in mats]),
                     work=stk([work(b) for b in mats]),
                     shape=b0.shape, group_size=b0.group_size, bits=b0.bits,
                     m=max(b.m for b in mats), block_n=b0.block_n,
                     lane=b0.lane)


def to_dense(bsr: BSRMatrix, dtype=jnp.float32) -> jnp.ndarray:
    """Decompress to dense [..., N, K] (pruned groups = 0)."""
    n, k = bsr.shape
    g = bsr.group_size
    deq = ((codes(bsr.words).astype(jnp.float32) - bsr.zero[..., None])
           * bsr.scale[..., None])                              # [.., Mp, G]
    # column c of row n reads its slot pos[n, c]; pruned columns read 0
    taken = jnp.take_along_axis(deq, jnp.maximum(bsr.pos, 0)[..., None],
                                axis=-2)                        # [.., Cp, G]
    dense = jnp.where(bsr.pos[..., None] >= 0, taken, 0.0)
    dense = dense[..., :n, :k // g, :]
    return dense.reshape(dense.shape[:-2] + (k,)).astype(dtype)


def to_paper_bsr(bsr: BSRMatrix):
    """Padded form -> the paper's exact (rowIndex, groups, values) arrays
    (numpy; used for storage accounting and format tests)."""
    n, m = bsr.shape[0], bsr.m
    idx = bsr.idx
    vals = np.asarray(pack_int4(codes(bsr.words)[:n, :m].astype(jnp.uint8)))
    scale = np.asarray(bsr.scale)[:n, :m]
    zero = np.asarray(bsr.zero)[:n, :m]
    keep = idx >= 0                                     # [N, M] bool
    # rowIndex = exclusive prefix sum of per-row kept-group counts
    row_index = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(keep.sum(axis=1), out=row_index[1:])
    # padded slots are right-aligned after the sorted kept columns, so a
    # row-major boolean gather preserves (row, sorted-col) order exactly
    groups = idx[keep].astype(np.int32)
    values = vals[keep]
    if values.size == 0:
        values = np.zeros((0, bsr.group_size // 2), np.uint8)
    return (row_index, groups, values,
            scale[keep].astype(np.float32), zero[keep].astype(np.float32))


def paper_bsr_nbytes(row_index, groups, values, scales, zeros,
                     bits: int = 4) -> int:
    """Exact ragged-format byte count (int16 group cols suffice for K/G<2^15,
    fp16 scale + u8 zero as deployed)."""
    return int(row_index.shape[0] * 4 + groups.shape[0] * 2
               + values.size + scales.shape[0] * 2 + zeros.shape[0] * 1)


# ---------------------------------------------------------------------------
# Task-centric work list (paper §3.5, Stream-K adapted to the TPU grid).
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class WorkList:
    """Flattened, equal-size work items for the sparse kernel.

    Each item = (row_block r, slot range [chunk*BM, chunk*BM+BM) of the padded
    group slots of that row block). Ragged rows make the number of *useful*
    chunks vary per row block; flattening them into one 1-D grid equalizes
    per-step latency (the TPU analogue of Stream-K's work-centric
    decomposition). ``first`` marks items that initialize their row block,
    ``last`` the item that completes it.
    """
    row_block: np.ndarray    # [W] int32
    chunk: np.ndarray        # [W] int32
    first: np.ndarray        # [W] int32 (1 = first visit of this row block)
    last: np.ndarray         # [W] int32 (1 = final visit of this row block)
    n_items: int


def build_work_list(idx, block_n: int, block_m: int) -> WorkList:
    """idx: [N, M] padded kept-group columns (-1 pad). Static (numpy) build --
    runs offline at pack time, like the paper's pre-processing."""
    idx_np = np.asarray(idx)
    n, m = idx_np.shape
    nrb = (n + block_n - 1) // block_n
    rows, chunks, firsts, lasts = [], [], [], []
    for r in range(nrb):
        blk = idx_np[r * block_n:(r + 1) * block_n]
        useful = int((blk >= 0).sum(axis=1).max()) if blk.size else 0
        nch = max(1, (useful + block_m - 1) // block_m)
        for c in range(nch):
            rows.append(r)
            chunks.append(c)
            firsts.append(1 if c == 0 else 0)
            lasts.append(1 if c == nch - 1 else 0)
    arr = lambda v: np.asarray(v, np.int32)
    return WorkList(row_block=arr(rows), chunk=arr(chunks),
                    first=arr(firsts), last=arr(lasts), n_items=len(rows))
