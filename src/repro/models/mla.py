"""Multi-head Latent Attention (DeepSeek-V2, arXiv:2405.04434).

KV is compressed to a rank-`kv_lora_rank` latent + one shared RoPE key head;
the decode cache stores only [B, S, kv_lora_rank + qk_rope_dim] — the MLA
memory win. Decode uses the *absorbed* formulation: q_nope is projected
through W_UK once per step so scores contract directly against the latent
cache (no per-step K up-projection over the whole history).

GQSA note: w_qa/w_qb/w_kva/wo are GQS-compressible GEMVs; w_uk/w_uv are used
in per-head einsum form (absorbed path) and stay dense FP (~8M params each —
documented exclusion, DESIGN.md §6).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.gqs_layer import apply_linear
from repro.models.layers import (apply_rope, decode_attention,
                                 flash_attention, linear_init, norm_init,
                                 page_write, paged_block_geometry,
                                 paged_write_pages, rmsnorm)


def mla_init(rng, cfg, dtype=jnp.float32) -> Dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    ks = jax.random.split(rng, 7)
    return {
        "w_qa": linear_init(ks[0], m.q_lora_rank, d, dtype),
        "q_norm": norm_init(m.q_lora_rank, dtype),
        "w_qb": linear_init(ks[1], h * (m.qk_nope_dim + m.qk_rope_dim),
                            m.q_lora_rank, dtype),
        "w_kva": linear_init(ks[2], m.kv_lora_rank + m.qk_rope_dim, d, dtype),
        "kv_norm": norm_init(m.kv_lora_rank, dtype),
        "w_uk": jax.random.normal(ks[3], (h, m.qk_nope_dim, m.kv_lora_rank),
                                  dtype) / jnp.sqrt(m.kv_lora_rank),
        "w_uv": jax.random.normal(ks[4], (h, m.v_dim, m.kv_lora_rank),
                                  dtype) / jnp.sqrt(m.kv_lora_rank),
        "wo": linear_init(ks[5], d, h * m.v_dim, dtype),
    }


def _mla_q(p: Dict, x: jnp.ndarray, positions, cfg, use_pallas):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    cq = rmsnorm(apply_linear(p["w_qa"], x, use_pallas=use_pallas),
                 p["q_norm"], cfg.norm_eps)
    q = apply_linear(p["w_qb"], cq, use_pallas=use_pallas)
    q = q.reshape(b, s, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_kv_latent(p: Dict, x: jnp.ndarray, positions, cfg, use_pallas):
    m = cfg.mla
    ckv_full = apply_linear(p["w_kva"], x, use_pallas=use_pallas)
    c_kv, k_rope = jnp.split(ckv_full, [m.kv_lora_rank], axis=-1)
    c_kv = rmsnorm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_rope


def mla_prefill_paged(p: Dict, x: jnp.ndarray, positions: jnp.ndarray, cfg,
                      use_pallas: bool = False
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Full-sequence MLA attention + the latent row each token pages.

    Returns (attn_out [B, S, d], latent [B, S, R + rope]). The latent
    row is EXACTLY what :func:`mla_decode`'s dense cache stores per
    position (post-norm ``c_kv`` ++ post-RoPE ``k_rope``), so a paged
    pool filled from it can be scored with the absorbed-W_UK decode path
    (:func:`mla_decode_paged`) and stays the dense path's parity twin.
    Attention itself is the unabsorbed flash form — prefill is
    compute-bound, so K/V are up-projected once for the whole sequence.
    """
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    q_nope, q_rope = _mla_q(p, x, positions, cfg, use_pallas)
    c_kv, k_rope = _mla_kv_latent(p, x, positions, cfg, use_pallas)

    k_nope = jnp.einsum("bsr,hdr->bshd", c_kv,
                        p["w_uk"].astype(c_kv.dtype))
    v = jnp.einsum("bsr,hvr->bshv", c_kv, p["w_uv"].astype(c_kv.dtype))
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, h, m.qk_rope_dim))], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    o = flash_attention(q, k, v, causal=True,
                        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                        unroll=cfg.analysis_unroll)
    out = apply_linear(p["wo"], o.reshape(b, s, -1), use_pallas=use_pallas)
    return out, jnp.concatenate([c_kv, k_rope], axis=-1)


def mla_block(p: Dict, x: jnp.ndarray, positions: jnp.ndarray, cfg,
              use_pallas: bool = False) -> jnp.ndarray:
    """Full-sequence MLA (train / prefill). x: [B, S, d]."""
    out, _ = mla_prefill_paged(p, x, positions, cfg, use_pallas)
    return out


def mla_cache_init(cfg, batch: int, max_seq: int, dtype) -> Dict:
    m = cfg.mla
    return {"c_kv": jnp.zeros((batch, max_seq, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_seq, m.qk_rope_dim), dtype)}


def _absorbed_q(p: Dict, q_nope: jnp.ndarray, q_rope: jnp.ndarray, cfg
                ) -> jnp.ndarray:
    """Absorb W_UK into q so scores contract against the latent directly:
    [B, T, H, nope/rope] -> pre-scaled [B, T, H, R + rope]. The score
    scale must match the UNABSORBED head dim, so q carries the
    sqrt(fake/true) correction (attention kernels divide by
    sqrt(R + rope))."""
    m = cfg.mla
    q_lat = jnp.einsum("bshd,hdr->bshr", q_nope,
                       p["w_uk"].astype(q_nope.dtype))       # [B,T,H,R]
    # treat latent + rope as a single KV head of dim R + rope
    q_cat = jnp.concatenate([q_lat, q_rope], axis=-1)        # [B,T,H,R+rope]
    true_dim = m.qk_nope_dim + m.qk_rope_dim
    fake_dim = m.kv_lora_rank + m.qk_rope_dim
    return q_cat * jnp.sqrt(fake_dim / true_dim).astype(q_cat.dtype)


def mla_decode(p: Dict, x: jnp.ndarray, cache: Dict, pos, cfg,
               use_pallas: bool = False) -> Tuple[jnp.ndarray, Dict]:
    """Absorbed single-step decode. x: [B, 1, d]."""
    m = cfg.mla
    b = x.shape[0]
    positions = jnp.full((b, 1), pos, jnp.int32)
    q_nope, q_rope = _mla_q(p, x, positions, cfg, use_pallas)
    c_kv_new, k_rope_new = _mla_kv_latent(p, x, positions, cfg, use_pallas)

    c_kv = jax.lax.dynamic_update_slice(
        cache["c_kv"], c_kv_new.astype(cache["c_kv"].dtype), (0, pos, 0))
    k_rope = jax.lax.dynamic_update_slice(
        cache["k_rope"], k_rope_new.astype(cache["k_rope"].dtype),
        (0, pos, 0))

    q_scaled = _absorbed_q(p, q_nope, q_rope, cfg)
    k_cat = jnp.concatenate([c_kv, k_rope], axis=-1)[:, :, None, :]
    ctx = decode_attention(q_scaled, k_cat, c_kv[:, :, None, :], pos + 1)
    # ctx: [B,1,H,R] -> per-head value up-projection
    v = jnp.einsum("bshr,hvr->bshv", ctx, p["w_uv"].astype(ctx.dtype))
    return apply_linear(p["wo"], v.reshape(b, 1, -1), use_pallas=use_pallas)\
        , {"c_kv": c_kv, "k_rope": k_rope}


def mla_decode_paged(p: Dict, x: jnp.ndarray, cache: Dict,
                     block_tables: jnp.ndarray, positions: jnp.ndarray,
                     cfg, use_pallas: bool = False,
                     tree: Optional[Dict] = None,
                     feed_len: Optional[jnp.ndarray] = None
                     ) -> Tuple[jnp.ndarray, Dict]:
    """T-token absorbed MLA decode against the PAGED latent pool (one
    layer's view) — the mla_moe twin of
    `models/layers.py:attention_decode_paged` (DESIGN.md §9).

    x: [B, T, d]; positions: [B] write position of each slot's first
    token; block_tables: [B, MP] page ids (>= P entries are sentinels);
    cache: ``{"lat_pages": [1, P, ps, R + rope]}`` — ONE logical KV "head"
    per page pool holding post-norm ``c_kv`` ++ post-RoPE ``k_rope``.
    There is NO V pool: the value of a cached token is the leading R
    dims of the same latent row, up-projected through W_UV only AFTER
    attention — so paging the latent pays one pool instead of K + V.

    T=1 is plain continuous-batching decode, T=K+1 the speculative
    verify staircase, and ``tree`` the token-tree block (RoPE at tree
    depth, ancestor-bitmap masking — identical semantics to the GQA
    path, shared via :func:`layers.paged_block_geometry`).

    ``use_pallas`` routes the attention through the fused latent kernel
    (`kernels/ops.py:paged_latent_attention`); the jnp path gathers
    pages densely and reuses
    :func:`decode_attention`, the same op sequence as the dense
    :func:`mla_decode` oracle.
    """
    m = cfg.mla
    b, t, _ = x.shape
    lat = cache["lat_pages"]
    page_size = lat.shape[2]
    pos_bt, rope_pos, length, base, anc, window = paged_block_geometry(
        positions, t, tree)
    q_nope, q_rope = _mla_q(p, x, rope_pos, cfg, use_pallas)
    c_kv_new, k_rope_new = _mla_kv_latent(p, x, rope_pos, cfg, use_pallas)
    lat_new = jnp.concatenate([c_kv_new, k_rope_new], axis=-1)   # [B,T,R+r]

    page = paged_write_pages(block_tables, pos_bt, page_size, lat.shape[1],
                             feed_len)
    off = pos_bt % page_size
    new = {"lat_pages": page_write(lat, page, off, lat_new[:, :, None])}

    q_scaled = _absorbed_q(p, q_nope, q_rope, cfg)           # [B,T,H,R+r]
    if use_pallas:
        from repro.kernels import ops as kops
        ctx = kops.paged_latent_attention(
            q_scaled, new["lat_pages"], length, block_tables,
            v_rank=m.kv_lora_rank, anc=anc, anc_base=base,
            anc_window=window).astype(q_scaled.dtype)
    else:
        from repro.kernels.ref import page_view
        g = page_view(new["lat_pages"], block_tables)   # [B, S, 1, R+r]
        ctx = decode_attention(q_scaled, g, g[..., :m.kv_lora_rank], length,
                               anc, base, window)
    v = jnp.einsum("bshr,hvr->bshv", ctx, p["w_uv"].astype(ctx.dtype))
    return apply_linear(p["wo"], v.reshape(b, t, -1),
                        use_pallas=use_pallas), new
