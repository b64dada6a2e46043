"""Fused paged-attention decode kernel — Pallas TPU (DESIGN.md §7).

Decode attention computed *in place* on the paged KV pool: no dense
per-step page gather. The engine's previous hot path materialized every
slot's block-table pages into a `[B, MP*ps, KH, D]` copy each layer,
each step — O(B * max_pages) HBM traffic regardless of how long the
sequences actually are. This kernel streams only the *live* pages of
each slot through VMEM, so per-step attention traffic is O(live tokens).

Layouts (one layer's view of the pool, head-major so each grid step
DMAs one tileable [ps, D] (head, page) tile):
    q             [B, KH, TRp, D]      query rows grouped by KV head,
                                       T-major inside the row dim, padded
                                       from T*R to the sublane multiple
    k/v_pages     [KH, P, ps, D]       bf16/f32, or int8 with
    k/v_scale     [KH, P, ps, 1] f32   per-token x head scales
    lengths       [B, T] int32         per-query valid prefix (staircase:
                                       query t of a slot sees cache
                                       positions < lengths[b, t]); read
                                       from SMEM
    block_tables  [B, MP] int32        page ids; entries >= P are
                                       out-of-range sentinels
    live          [B] int32            number of live pages per slot
                                       (= ceil(max_t lengths / ps))
    out           [B, KH, T*R, D] f32

Grid: (B, KH, MP) — pages innermost so the (m, l, acc) VMEM scratch
carries the online softmax across one slot/kv-head's page stream.

Three mechanisms kill the dense gather's waste:

* **Scalar-prefetched block tables drive the DMA.** The K/V BlockSpec
  index maps read `block_tables[b, page_idx]` directly, so each grid
  step fetches one *pool page* — the copy to a dense per-slot buffer
  never exists.
* **Dead pages are never fetched.** For grid steps past a slot's live
  page count the index map clamps to the last live page; Pallas elides
  the DMA when consecutive steps map to the same block, and `pl.when`
  skips the compute entirely. Sentinel entries (>= P) clamp to page
  P - 1 — exactly XLA's OOB-gather clip, so the jnp reference and the
  kernel read identical (masked) garbage and stay bit-comparable.
* **int8 pages dequantize on VMEM tiles.** HBM traffic is the int8
  payload (half of bf16); the f32 dequant + contraction happen on the
  in-VMEM tile, mirroring the contiguous int8 decode kernel this module
  absorbed (the former ``kv_decode.py``; see
  :func:`ops.kv_decode_attention` for the degenerate one-page-table
  wrapper). The score/value contractions stay f32-after-dequant — an
  online softmax cannot know the global softmax-weight amax that the
  jnp ``decode_attention_int8`` path uses to re-quantize p, and the
  dequant form is what keeps the folded contiguous parity at 1e-4.

T > 1 covers the speculative-decoding verify step (T = K+1 per-slot
short-prefill): causality inside the block comes from the per-query
staircase ``lengths``, identical to the jnp reference's masking.

Token-TREE verification (DESIGN.md §8) adds an optional ancestor-bitmap
operand: the fed block is a flat BFS token tree written at cache
positions ``base .. base + window - 1``, and query t additionally
requires bit ``s - base`` of ``anc[b, t]`` for cache positions inside
that window — siblings/uncles in the block stay invisible. ``anc``
[B, T] and ``base`` [B] ride the scalar-prefetch path next to the block
tables and ``lengths``; each query row's length and bitmap are selected
from SMEM scalars (row i belongs to query i // R). With ``anc`` absent
the compiled kernel is UNCHANGED (the staircase is the chain special
case — `models/layers.py:ancestor_mask` is the shared mask definition).

MLA LATENT pages (DESIGN.md §9) are the ``v_pages=None`` mode: the pool
holds one [ps, kv_lora_rank + qk_rope_dim] latent row per token (a
single logical KV head, [1, P, ps, Dl]), the value operand IS the key
page (no V pool — callers up-project through W_UV after slicing the
leading R dims of the output). The 576-lane latent row is one full-width
block; Mosaic tiles its contraction.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(bt_ref, live_ref, len_ref,               # scalar prefetch
            q_ref, k_ref, v_ref,                    # VMEM in (bf16/f32)
            o_ref,                                  # VMEM out
            m_ref, l_ref, acc_ref,                  # scratch
            *, page_size: int, t: int, r: int,
            ks_ref=None, vs_ref=None,
            anc_ref=None, base_ref=None, window: int = 0):
    # ``v_ref is None`` is the LATENT mode (MLA, DESIGN.md §9): the pool
    # holds one latent row per token and the value IS that row (callers
    # slice the leading kv_lora_rank dims of the output) — V = K, one
    # page fetch instead of two.
    bi = pl.program_id(0)
    pi = pl.program_id(2)
    npg = pl.num_programs(2)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # pages at/above the live count were not (re)fetched — skip compute
    @pl.when(pi < live_ref[bi])
    def _update():
        q = q_ref[0, 0].astype(jnp.float32)          # [TRp, D]
        trp, d = q.shape
        scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)

        # dequantize this page tile in VMEM (HBM traffic stays int8)
        k = k_ref[0, 0].astype(jnp.float32)          # [ps, D]
        v = k if v_ref is None else v_ref[0, 0].astype(jnp.float32)
        if ks_ref is not None:
            k = k * ks_ref[0, 0]                     # [ps, 1] per token
            v = v * vs_ref[0, 0]
        sco = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [TRp, ps]

        # per-query staircase mask: query t sees positions < lengths[b, t].
        # Row i of the tile is query i // r; rows past T*r are sublane
        # padding and stay fully masked (length 0).
        shape = (trp, page_size)
        row_t = jax.lax.broadcasted_iota(jnp.int32, shape, 0) // r
        pos = pi * page_size + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        lq = jnp.zeros(shape, jnp.int32)
        for tt in range(t):
            lq = jnp.where(row_t == tt, len_ref[bi * t + tt], lq)
        valid = pos < lq
        if anc_ref is not None:
            # token-tree window: positions base..base+window-1 hold the
            # fed BFS block; query t sees only its ancestor bits there
            aq = jnp.zeros(shape, jnp.int32)
            for tt in range(t):
                aq = jnp.where(row_t == tt, anc_ref[bi * t + tt], aq)
            fed = pos - base_ref[bi]
            in_win = (fed >= 0) & (fed < window)
            bits = (aq >> jnp.clip(fed, 0, 31)) & 1
            valid &= jnp.logical_not(in_win) | (bits == 1)
        sco = jnp.where(valid, sco, -jnp.inf)

        m_old = m_ref[:, 0]
        m_new = jnp.maximum(m_old, jnp.max(sco, axis=-1))
        m_safe = jnp.where(jnp.isinf(m_new), 0.0, m_new)
        p = jnp.exp(sco - m_safe[:, None])
        p = jnp.where(jnp.isinf(sco), 0.0, p)
        corr = jnp.exp(jnp.where(jnp.isinf(m_old), -jnp.inf, m_old) - m_safe)
        corr = jnp.where(jnp.isinf(m_old), 0.0, corr)

        l_new = l_ref[:, 0] * corr + jnp.sum(p, axis=-1)
        acc_new = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)
        acc_ref[...] = acc_new

    @pl.when(pi == npg - 1)
    def _finalize():
        # fully-masked rows (length 0, e.g. row padding) have l == 0 and
        # finalize to exact zeros rather than NaN
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[:, 0], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


def paged_attention_pallas(
    q: jnp.ndarray,            # [B, KH, TRp, D]
    k_pages: jnp.ndarray,      # [KH, P, ps, D] bf16/f32/int8
    v_pages: jnp.ndarray,
    lengths: jnp.ndarray,      # [B, T] int32 per-query valid prefix
    block_tables: jnp.ndarray,  # [B, MP] int32 (>= P entries = sentinel)
    live_pages: jnp.ndarray,   # [B] int32 live page count per slot
    k_scale_pages=None,        # [KH, P, ps, 1] f32 (int8 pages only)
    v_scale_pages=None,
    *,
    t: int,
    r: int,
    anc=None,                  # [B, T] int32 ancestor bitmaps (tree verify)
    anc_base=None,             # [B] int32 cache position of the tree root
    anc_window: int = 0,       # fed-block width (bits used in anc)
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns [B, KH, TRp, D] f32. See module docstring for semantics.

    ``v_pages=None`` selects the LATENT mode (MLA latent pool, one
    logical KV head): the value operand is the key page itself, so each
    grid step DMAs one pool page instead of two; callers slice the
    leading ``kv_lora_rank`` dims of the output
    (`ops.paged_latent_attention`)."""
    b, khn, trp, d = q.shape
    num_pages, page_size = k_pages.shape[1], k_pages.shape[2]
    mp = block_tables.shape[1]
    int8 = k_scale_pages is not None
    tree = anc is not None
    latent = v_pages is None
    if latent and int8:
        raise NotImplementedError("int8 latent pages are a recorded "
                                  "follow-on (ROADMAP)")
    grid = (b, khn, mp)

    # index maps take the scalar-prefetch operands after the grid ids
    # (block tables, live counts, lengths[, anc, base])
    def page_map(bi, ki, pi, bt, live, *_):
        # steps past the live prefix re-map to the last live page so the
        # block index is unchanged and Pallas elides the DMA; sentinel
        # entries clamp to P - 1 (== XLA's OOB-gather clip)
        pe = jnp.minimum(pi, jnp.maximum(live[bi] - 1, 0))
        return (ki, jnp.minimum(bt[bi, pe], num_pages - 1), 0, 0)

    def row_map(bi, ki, pi, *_):
        return (bi, ki, 0, 0)

    prefetch = [block_tables.astype(jnp.int32),
                live_pages.astype(jnp.int32),
                lengths.astype(jnp.int32).reshape(-1)]
    if tree:
        prefetch += [anc.astype(jnp.int32).reshape(-1),
                     anc_base.astype(jnp.int32)]
    page_spec = pl.BlockSpec((1, 1, page_size, d), page_map)
    in_specs = [pl.BlockSpec((1, 1, trp, d), row_map), page_spec]
    args = [q, k_pages]
    if not latent:
        in_specs.append(page_spec)
        args.append(v_pages)
    if int8:
        in_specs += [pl.BlockSpec((1, 1, page_size, 1), page_map)] * 2
        args += [k_scale_pages, v_scale_pages]

    def kern(*refs):
        anc_ref, base_ref = (refs[3], refs[4]) if tree else (None, None)
        i = len(prefetch)
        q_ref, k_ref = refs[i:i + 2]; i += 2
        v_ref = None
        if not latent:
            v_ref = refs[i]; i += 1
        ks_ref = vs_ref = None
        if int8:
            ks_ref, vs_ref = refs[i:i + 2]; i += 2
        o_ref, m_ref, l_ref, acc_ref = refs[i:i + 4]
        return _kernel(refs[0], refs[1], refs[2], q_ref, k_ref, v_ref,
                       o_ref, m_ref, l_ref, acc_ref,
                       page_size=page_size, t=t, r=r,
                       ks_ref=ks_ref, vs_ref=vs_ref,
                       anc_ref=anc_ref, base_ref=base_ref,
                       window=anc_window)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, trp, d), row_map),
        scratch_shapes=[
            pltpu.VMEM((trp, 128), jnp.float32),   # running max (lane-padded)
            pltpu.VMEM((trp, 128), jnp.float32),   # running denom
            pltpu.VMEM((trp, d), jnp.float32),     # unnormalized output
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, khn, trp, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(*prefetch, *args)
