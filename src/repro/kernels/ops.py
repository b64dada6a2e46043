"""Public kernel entry points: activation padding and layout plumbing.

Every entry point runs its Pallas kernel: compiled on the TPU, interpreted
on the CPU (the only platform where interpret mode runs). The weights and
KV pools arrive in the layouts the kernels read — built at pack time
(core/bsr.py, core/gqs_layer.py:pack_w4) or by ``init_paged_cache`` — so
per call only activations are padded or permuted. The pure-jnp oracles
live in kernels/ref.py; choosing between them and these kernels is the
model's ``use_pallas`` switch, never this module's.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bsr import BSRMatrix
from repro.kernels.gqsa_gemv import (gqsa_densify_experts_pallas,
                                     gqsa_densify_pallas,
                                     gqsa_gemv_experts_pallas,
                                     gqsa_gemv_pallas)
from repro.kernels.gqsa_gemv import DEFAULT_BLOCK_T as DEFAULT_GEMV_BLOCK_T
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.w4_matmul import (w4_matmul_pallas, DEFAULT_BLOCK_T,
                                     DEFAULT_BLOCK_N, DEFAULT_BLOCK_R)


def _interpret() -> bool:
    """Kernels compile for the TPU; only the CPU runs them interpreted."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(f"no Pallas path for backend {backend!r}")
    return backend == "cpu"


def _pad_to(x: jnp.ndarray, axis: int, mult: int, value=0) -> jnp.ndarray:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _row_tile(t: int, cap: int) -> int:
    """Rows per grid step: all of them up to ``cap`` (sublane-rounded),
    else ``cap`` — the caller pads T to a multiple of the result."""
    return min(cap, -(-t // 8) * 8)


def gqsa_gemv(x: jnp.ndarray, bsr: BSRMatrix,
              label: str = "") -> jnp.ndarray:
    """y = x @ dense(bsr).T from the packed weight. Returns [T, N] f32.

    x: [T, K], any T. The row count picks the schedule:

    * T <= DEFAULT_BLOCK_T rows (decode, verify, short prefill) fit one
      activation tile: the fused kernel ``gqsa_gemv_<label>`` densifies
      each weight row block in VMEM and multiplies it there, so HBM
      traffic is the packed payload.
    * More rows would make the fused kernel densify every row block again
      for each 256-row tile. Instead ``gqsa_densify_<label>`` densifies
      the weight once, into an [Np, K] transient of x's dtype in HBM that
      lives only for this call, and one XLA matmul contracts it with all
      rows at f32 accumulation.

    Both run the same placement code on the same operands; only the
    summation order differs. The activations are permuted to the
    kernels' group-column-major order. ``label`` (static) names the
    linear in the kernels' names.
    """
    t, k = x.shape
    n = bsr.shape[0]
    g = bsr.group_size
    cp = bsr.pos.shape[-1]
    xg = x.reshape(t, k // g, g).transpose(0, 2, 1)           # [T, G, C]
    if t > DEFAULT_GEMV_BLOCK_T:
        wd = gqsa_densify(bsr, x.dtype, label)
        # HIGHEST keeps f32 activations' products in f32, as the fused
        # kernel does; bf16 operands take one MXU pass either way
        y = jax.lax.dot_general(xg.reshape(t, k), wd,
                                (((1,), (1,)), ((), ())),
                                precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
        return y[:, :n]
    xg = _pad_to(xg, 2, cp).reshape(t, g * cp)
    bt = _row_tile(t, DEFAULT_GEMV_BLOCK_T)
    y = gqsa_gemv_pallas(_pad_to(xg, 0, bt), bsr.words, bsr.scale, bsr.zero,
                         bsr.pos, bsr.work, group_size=g,
                         block_n=bsr.block_n, lane=bsr.lane, block_t=bt,
                         label=label, interpret=_interpret())
    return y[:t, :n]


def gqsa_densify(bsr: BSRMatrix, dtype, label: str = "") -> jnp.ndarray:
    """The weight of ``bsr`` dense in HBM, [Np, K] of ``dtype`` with its
    columns in group-column-major order (:func:`group_major` orders the
    activations to match), by the kernel ``gqsa_densify_<label>``."""
    k = bsr.shape[1]
    g = bsr.group_size
    return gqsa_densify_pallas(bsr.words, bsr.scale, bsr.zero, bsr.pos,
                               bsr.work, group_size=g, block_n=bsr.block_n,
                               lane=bsr.lane, c=k // g, dtype=dtype,
                               label=label, interpret=_interpret())


def group_major(x: jnp.ndarray, group_size: int) -> jnp.ndarray:
    """[T, K] activations in the column order of :func:`gqsa_densify`."""
    t, k = x.shape
    return x.reshape(t, k // group_size, group_size).transpose(
        0, 2, 1).reshape(t, k)


def gqsa_gemv_experts(x: jnp.ndarray, bsr: BSRMatrix,
                      label: str = "") -> jnp.ndarray:
    """y[e] = x[e] @ dense(bsr[e]).T for a stack of E experts, by one
    fused kernel ``gqsa_gemv_<label>`` over all of them: x [E, T, K]
    with T at most DEFAULT_GEMV_BLOCK_T (decode), bsr with leaves led
    by E. Returns [E, T, N] f32."""
    e, t, k = x.shape
    n = bsr.shape[0]
    g = bsr.group_size
    cp = bsr.pos.shape[-1]
    if t > DEFAULT_GEMV_BLOCK_T:
        raise ValueError(f"{t} rows per expert: the expert GEMV serves "
                         f"one activation tile ({DEFAULT_GEMV_BLOCK_T})")
    xg = x.reshape(e, t, k // g, g).transpose(0, 1, 3, 2)      # [E,T,G,C]
    xg = _pad_to(xg, 3, cp).reshape(e, t, g * cp)
    bt = _row_tile(t, DEFAULT_GEMV_BLOCK_T)
    y = gqsa_gemv_experts_pallas(_pad_to(xg, 1, bt), bsr.words, bsr.scale,
                                 bsr.zero, bsr.pos, bsr.work, group_size=g,
                                 block_n=bsr.block_n, lane=bsr.lane,
                                 block_t=bt, label=label,
                                 interpret=_interpret())
    return y[:, :t, :n]


def gqsa_densify_experts(bsr: BSRMatrix, dtype,
                         label: str = "") -> jnp.ndarray:
    """:func:`gqsa_densify` of a stack of E experts by one kernel
    ``gqsa_densify_<label>``: [E, Np, K], columns group-column-major."""
    k = bsr.shape[1]
    g = bsr.group_size
    return gqsa_densify_experts_pallas(
        bsr.words, bsr.scale, bsr.zero, bsr.pos, bsr.work, group_size=g,
        block_n=bsr.block_n, lane=bsr.lane, c=k // g, dtype=dtype,
        label=label, interpret=_interpret())


def grouped_matmul(xs: jnp.ndarray, w: jnp.ndarray,
                   sizes: jnp.ndarray) -> jnp.ndarray:
    """Rows of ``xs`` [M, K], sorted by group, each times its group's
    ``w`` [E, N, K] transposed: the grouped (megablox) matmul kernel.
    ``sizes`` [E] counts each group's rows; rows past their sum are
    zero. Returns [M, N] f32."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm
    m, k = xs.shape
    n = w.shape[1]
    tm = 128
    y = gmm(_pad_to(xs, 0, tm), w, sizes, preferred_element_type=jnp.float32,
            tiling=(tm, _tile(k), _tile(n)), transpose_rhs=True,
            interpret=_interpret())[:m]
    rows = jnp.arange(m) < jnp.sum(sizes)
    return jnp.where(rows[:, None], y, 0.0)


def _tile(size: int) -> int:
    """A grouped-matmul tile of a K or N dim: 512 where it divides, else
    the largest multiple of 128 that does."""
    for t in (512, 384, 256, 128):
        if size % t == 0:
            return t
    return size


def _block_r(rows: int, group_size: int) -> int:
    """Word rows per K step: the largest multiple of the (8, 128) tile
    unit that divides ``rows`` and is at most DEFAULT_BLOCK_R, else all."""
    unit = max(128, 8 * group_size)
    for br in range(DEFAULT_BLOCK_R - DEFAULT_BLOCK_R % unit, 0, -unit):
        if rows % br == 0:
            return br
    return rows


def w4_matmul(x: jnp.ndarray, qw: jnp.ndarray, scale: jnp.ndarray,
              zero: jnp.ndarray) -> jnp.ndarray:
    """y = x @ deq(qw).T for the plane-packed W4 layout of
    ``kernels/w4_matmul.py``. x: [T, K] -> [T, N] f32."""
    t, k = x.shape
    rows, n = qw.shape                       # rows = Kp / 8
    g = rows // scale.shape[1]
    xp = jnp.pad(x, ((0, 0), (0, 8 * rows - k)))
    bt = _row_tile(t, DEFAULT_BLOCK_T)
    xp = _pad_to(xp, 0, bt)
    x3 = xp.reshape(-1, 8, rows).transpose(1, 0, 2)          # [8, Tp, Kp/8]
    bn = DEFAULT_BLOCK_N if n % DEFAULT_BLOCK_N == 0 else n
    y = w4_matmul_pallas(x3, qw, scale, zero, group_size=g, block_t=bt,
                         block_n=bn, block_r=_block_r(rows, g),
                         interpret=_interpret())
    return y[:t]


def gemv_bytes_model(bsr: BSRMatrix, batch: int = 1) -> dict:
    """Static byte-traffic model for the roofline (per call, per chip):
    everything the kernel DMAs from HBM once, at *deployed* widths
    (paper/gguf convention: int16 group index, fp16 scale, u8 zero —
    the padded in-memory form above uses wider dev-side types)."""
    n, k = bsr.shape
    m = bsr.m
    g = bsr.group_size
    payload = n * m * (g * bsr.bits // 8 + 2 + 2 + 1)
    x_bytes = batch * k * 2           # bf16 activations
    y_bytes = batch * n * 4
    flops = 2 * batch * n * m * g
    return dict(weight_bytes=payload, act_bytes=x_bytes + y_bytes,
                total_bytes=payload + x_bytes + y_bytes, flops=flops)


def dense_bytes_model(n: int, k: int, batch: int = 1,
                      bits: int = 16, group_size: int = 0) -> dict:
    """Byte model for dense (fp16 / W4) GEMV for the fig6 comparison."""
    wbytes = n * k * bits // 8
    if group_size:
        wbytes += n * (k // group_size) * 3  # fp16 scale + u8 zero
    x_bytes = batch * k * 2
    y_bytes = batch * n * 4
    return dict(weight_bytes=wbytes, act_bytes=x_bytes + y_bytes,
                total_bytes=wbytes + x_bytes + y_bytes,
                flops=2 * batch * n * k)


def _paged_query_prep(lengths, block_tables, b: int, t: int,
                      page_size: int):
    """Shared preamble of the paged-attention dispatchers: broadcast the
    [] / [B] / [B, T] length spec to the kernel's [B, T] row operand and
    derive the live-page counts the scalar prefetch consumes — ONE
    definition so the GQA and latent entry points can never
    desynchronize on the rounding/sentinel convention."""
    from repro.models.layers import _query_lengths
    lq = _query_lengths(lengths, b, t).astype(jnp.int32)     # [B, T]
    mp = block_tables.shape[1]
    live = jnp.clip(
        (jnp.max(lq, axis=1) + page_size - 1) // page_size, 0, mp)
    return lq, live


def paged_decode_attention(q, k_pages, v_pages, lengths, block_tables,
                           k_scale_pages=None, v_scale_pages=None, *,
                           anc=None, anc_base=None, anc_window: int = 0):
    """Fused decode attention directly on the paged KV pool.

    q: [B, T, H, D] (T=1 continuous-batching decode; T=K+1 speculative
    verify); k/v_pages: [KH, P, ps, D] head-major (bf16/f32, or int8 with
    f32 [KH, P, ps, 1] scale pages); lengths: [] / [B] / [B, T] per-query
    valid prefix (the multi-token staircase); block_tables: [B, MP] page
    ids, entries >= P are out-of-range sentinels. Returns [B, T, H, D] f32.

    ``anc`` [B, T] / ``anc_base`` [B] / ``anc_window`` switch the fed
    block to token-TREE semantics (`models/layers.py:ancestor_mask`):
    query t additionally needs bit ``s - anc_base[b]`` of ``anc[b, t]``
    for cache positions inside the fed window.

    The kernel streams only each slot's live pages through VMEM —
    O(live tokens) HBM traffic; the dense-gather oracle is
    `kernels/ref.py:paged_attention_ref` (identical math).
    """
    b, t, h, d = q.shape
    page_size = k_pages.shape[2]
    khn = k_pages.shape[0]
    r = h // khn
    lq, live = _paged_query_prep(lengths, block_tables, b, t, page_size)
    # kernel row layout: [B, KH, T*R, D], T-major inside the row dim,
    # padded to the sublane multiple (padding rows are fully masked)
    qh = q.reshape(b, t, khn, r, d).transpose(0, 2, 1, 3, 4) \
          .reshape(b, khn, t * r, d)
    o = paged_attention_pallas(_pad_to(qh, 2, 8), k_pages, v_pages, lq,
                               block_tables, live, k_scale_pages,
                               v_scale_pages, t=t, r=r, anc=anc,
                               anc_base=anc_base, anc_window=anc_window,
                               interpret=_interpret())
    return o[:, :, :t * r].reshape(b, khn, t, r, d).transpose(0, 2, 1, 3, 4) \
            .reshape(b, t, h, d)


def paged_latent_attention(q, lat_pages, lengths, block_tables, *,
                           v_rank: int, anc=None, anc_base=None,
                           anc_window: int = 0):
    """Fused decode attention on the paged MLA LATENT pool (DESIGN.md §9).

    q: [B, T, H, R + rope] absorbed-W_UK queries, PRE-SCALED by
    sqrt(fake/true) (`models/mla.py:_absorbed_q` — the kernel divides by
    sqrt(R + rope)); lat_pages: [1, P, ps, R + rope] — one logical KV
    head, post-norm c_kv ++ post-RoPE k_rope per token; lengths /
    block_tables / anc semantics exactly as :func:`paged_decode_attention`.
    Returns the latent context [B, T, H, v_rank] f32: the value of a
    cached token is the leading ``v_rank`` (= kv_lora_rank) dims of its
    latent row — there is no V pool, and W_UV is applied by the caller
    AFTER attention.

    The kernel shares the scalar-prefetch/block-table machinery of the
    GQA mode (``v_pages=None`` latent mode: V = K pages) and computes the
    full R + rope value columns (sliced here — column independence makes
    the leading dims identical); the dense-gather oracle is
    `kernels/ref.py:paged_latent_attention_ref`.
    """
    b, t, h, d = q.shape
    page_size = lat_pages.shape[2]
    lq, live = _paged_query_prep(lengths, block_tables, b, t, page_size)
    # kernel row layout: [B, KH=1, T*H, D], T-major inside the row dim
    qh = _pad_to(q.reshape(b, t * h, d)[:, None], 2, 8)
    o = paged_attention_pallas(qh, lat_pages, None, lq, block_tables, live,
                               t=t, r=h, anc=anc, anc_base=anc_base,
                               anc_window=anc_window, interpret=_interpret())
    return o[:, 0, :t * h].reshape(b, t, h, d)[..., :v_rank]


def kv_decode_attention(q, k_cache, k_scale, v_cache, v_scale, length, *,
                        block_s: int = 512):
    """int8-KV decode attention over a *contiguous* cache — the degenerate
    one-page-table case of the paged kernel: the [B, S, ...] cache is
    viewed as B*ceil(S/block_s) pages of ``block_s`` tokens with identity
    block tables. q: [B, KH, R, D] -> [B, KH, R, D] f32."""
    b, khn, r, d = q.shape
    s = k_cache.shape[1]
    block_s = min(block_s, s)
    npg = -(-s // block_s)

    def pages(buf):                      # [B, S, KH, ...] -> [KH, B*NP, bs, ...]
        buf = _pad_to(buf, 1, block_s)
        buf = buf.reshape((b * npg, block_s) + buf.shape[2:])
        return jnp.moveaxis(buf, 2, 0)

    bt = jnp.arange(b * npg, dtype=jnp.int32).reshape(b, npg)
    o = paged_decode_attention(
        q.reshape(b, 1, khn * r, d), pages(k_cache), pages(v_cache),
        jnp.broadcast_to(jnp.reshape(length, (-1,)), (b,)), bt,
        pages(k_scale[..., None]), pages(v_scale[..., None]))
    return o.reshape(b, khn, r, d)
