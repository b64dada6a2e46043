"""Pure-jnp oracles for the Pallas kernels: identical math, expressed as
gather + einsum. Models run them with ``use_pallas=False`` (the CPU
default, and the reference side of kernel comparisons on the chip).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.bsr import BSRMatrix, to_dense


def gqsa_gemv_ref(x: jnp.ndarray, bsr: BSRMatrix,
                  dtype=jnp.float32) -> jnp.ndarray:
    """Sparse-quantized GEMV / skinny GEMM.

    x: [B, K]  (B = decode slots, or slots x (K+1) draft rows in the
    speculative verify step)
    returns y: [B, N] with y[b,n] = sum_m deq(slot n,m) . x[b, col(n,m)G:+G]

    The surviving groups are dequantized once and placed into a dense
    f32 [N, K] operand (`core/bsr.py:to_dense`), then contracted with ONE
    matmul: traffic is row-count-independent.
    """
    y = x.astype(jnp.float32) @ to_dense(bsr).T
    return y.astype(dtype)


def w4_dense(qw: jnp.ndarray, scale: jnp.ndarray,
             zero: jnp.ndarray) -> jnp.ndarray:
    """Plane-packed W4 (`kernels/w4_matmul.py` layout) -> dense f32
    W^T [Kp, N]: nibble i of word r is input column i * Kp/8 + r."""
    rows, n = qw.shape
    g = rows // scale.shape[1]
    shifts = 4 * jnp.arange(8, dtype=jnp.int32)[:, None, None]
    q = ((qw[None] >> shifts) & 0xF).astype(jnp.float32)   # [8, Kp/8, N]
    w = (q.reshape(8, -1, g, n) - zero[:, :, None, :]) * scale[:, :, None, :]
    return w.reshape(8 * rows, n)


def w4_matmul_ref(x: jnp.ndarray, qw: jnp.ndarray, scale: jnp.ndarray,
                  zero: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """Dense grouped-dequant matmul (W4A16 baseline).

    x: [T, K]; qw/scale/zero: the plane-packed layout (K padded to Kp).
    y = x @ deq(qw).T
    """
    wt = w4_dense(qw, scale, zero)
    xp = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, wt.shape[0] - x.shape[1])))
    return (xp @ wt).astype(dtype)


def kv_decode_attention_ref(q, k_cache, k_scale, v_cache, v_scale, length,
                            dtype=jnp.float32):
    """Oracle for the int8-KV decode attention kernel.

    q: [B, KH, R, D]; k/v_cache: int8 [B, S, KH, D]; scales [B, S, KH].
    """
    b, s, khn, d = k_cache.shape
    k = k_cache.astype(jnp.float32) * k_scale[..., None]
    v = v_cache.astype(jnp.float32) * v_scale[..., None]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    sco = jnp.einsum("bkrd,bskd->bkrs", q.astype(jnp.float32), k) * scale
    pos = jnp.arange(s)
    valid = pos[None, :] < jnp.reshape(length, (-1, 1))
    sco = jnp.where(valid[:, None, None, :], sco, -jnp.inf)
    p = jax.nn.softmax(sco, axis=-1)
    o = jnp.einsum("bkrs,bskd->bkrd", p, v)
    return o.astype(dtype)


def page_view(pages: jnp.ndarray, block_tables: jnp.ndarray) -> jnp.ndarray:
    """Dense page gather: head-major pool [KH, P, ps, X] -> per-slot cache
    [B, MP*ps, KH, X]. Sentinel ids (>= P) clamp to P - 1 (XLA's
    OOB-gather clip); callers mask those positions by length."""
    tok_major = jnp.moveaxis(pages, 0, 2)              # [P, ps, KH, X]
    g = tok_major[jnp.minimum(block_tables, pages.shape[1] - 1)]
    return g.reshape((block_tables.shape[0], -1) + tok_major.shape[2:])


def paged_attention_ref(q, k_pages, v_pages, lengths, block_tables,
                        k_scale_pages=None, v_scale_pages=None,
                        dtype=jnp.float32, *, anc=None, anc_base=None,
                        anc_window: int = 0):
    """Oracle for the paged decode attention kernel.

    Dense page gather (what the kernel avoids) followed by staircase
    attention — identical math to the Pallas kernel: dequantize int8
    pages, f32 score/value contractions, per-query length mask.

    q: [B, T, H, D] (T=1 decode, T=K+1 speculative verify);
    k/v_pages: [KH, P, ps, D] (int8 variants add [KH, P, ps, 1] scales);
    lengths: [] / [B] / [B, T] per-query valid prefix; block_tables:
    [B, MP] page ids — entries >= P are sentinels and clamp to P - 1
    (XLA's OOB-gather clip), their positions masked by ``lengths``.
    ``anc``/``anc_base``/``anc_window``: optional token-tree ancestor
    bitmaps (`models/layers.py:ancestor_mask`; see
    :func:`tree_attention_ref`).
    Rows whose length is 0 softmax over an empty set and return NaN
    (the kernel returns 0 there); callers mask such rows either way.
    """
    from repro.models.layers import ancestor_mask
    b, t, h, d = q.shape
    khn, num_pages = k_pages.shape[:2]
    r = h // khn
    k = page_view(k_pages, block_tables).astype(jnp.float32)
    v = page_view(v_pages, block_tables).astype(jnp.float32)
    if k_scale_pages is not None:
        k = k * page_view(k_scale_pages, block_tables)
        v = v * page_view(v_scale_pages, block_tables)
    s = k.shape[1]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    qh = q.reshape(b, t, khn, r, d).astype(jnp.float32)
    sco = jnp.einsum("btkrd,bskd->bkrts", qh, k) * scale
    valid = ancestor_mask(lengths, anc, anc_base, anc_window,
                          b, t, s)                         # [B, T, S]
    sco = jnp.where(valid[:, None, None, :, :], sco, -jnp.inf)
    p = jax.nn.softmax(sco, axis=-1)                       # [B,KH,R,T,S]
    o = jnp.einsum("bkrts,bskd->btkrd", p, v)
    return o.reshape(b, t, h, d).astype(dtype)


def paged_latent_attention_ref(q, lat_pages, lengths, block_tables,
                               v_rank: int, dtype=jnp.float32, *,
                               anc=None, anc_base=None,
                               anc_window: int = 0):
    """Oracle for the paged LATENT attention kernel (MLA, DESIGN.md §9).

    q: [B, T, H, R + rope] absorbed pre-scaled queries; lat_pages:
    [1, P, ps, R + rope] — a single logical KV head whose value is the
    leading ``v_rank`` dims of the same row (no V pool). Dense page
    gather followed by single-head attention with the shared
    staircase/ancestor masks; sentinel block-table entries clamp to
    P - 1 exactly like :func:`paged_attention_ref`. Returns
    [B, T, H, v_rank].
    """
    from repro.models.layers import ancestor_mask
    b, t, h, d = q.shape
    k = page_view(lat_pages, block_tables)[:, :, 0].astype(
        jnp.float32)                                       # [B, S, R+rope]
    s = k.shape[1]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    sco = jnp.einsum("bthd,bsd->bhts", q.astype(jnp.float32), k) * scale
    valid = ancestor_mask(lengths, anc, anc_base, anc_window,
                          b, t, s)                         # [B, T, S]
    sco = jnp.where(valid[:, None, :, :], sco, -jnp.inf)
    p = jax.nn.softmax(sco, axis=-1)                       # [B, H, T, S]
    o = jnp.einsum("bhts,bsd->bthd", p, k[..., :v_rank])
    return o.astype(dtype)


def tree_attention_ref(q, k_pages, v_pages, lengths, block_tables,
                       anc, anc_base, anc_window: int,
                       k_scale_pages=None, v_scale_pages=None,
                       dtype=jnp.float32):
    """Oracle for token-TREE paged attention (DESIGN.md §8).

    The T fed queries are a flat BFS token tree written at cache
    positions ``anc_base .. anc_base + anc_window - 1``; ``anc`` [B, T]
    carries each query's root-to-self path as a bitmap over that window
    (bit i = BFS slot i visible). Everything else is
    :func:`paged_attention_ref` — the staircase is the degenerate chain
    (every bitmap a prefix of ones)."""
    return paged_attention_ref(q, k_pages, v_pages, lengths, block_tables,
                               k_scale_pages, v_scale_pages, dtype,
                               anc=anc, anc_base=anc_base,
                               anc_window=anc_window)
