"""Shared building blocks: norms, RoPE, attention (train flash + decode),
MLPs. All linears route through core.gqs_layer.apply_linear so every block
accepts FP, fake-quant, W4, or packed-GQSA parameters transparently.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.gqs_layer import apply_linear


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def linear_init(rng, n_out: int, n_in: int, dtype=jnp.float32,
                scale: Optional[float] = None) -> Dict:
    scale = scale if scale is not None else (1.0 / jnp.sqrt(n_in))
    w = jax.random.normal(rng, (n_out, n_in), dtype) * scale
    return {"w": w}


def norm_init(dim: int, dtype=jnp.float32) -> jnp.ndarray:
    return jnp.ones((dim,), dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jnp.ndarray, w: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)).astype(dt)


def layernorm(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray,
              eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * w + b).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                       dtype=jnp.float32) / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: [..., S, H, D]; positions: [..., S] (broadcastable)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                            # [D/2]
    ang = positions[..., None].astype(jnp.float32) * freqs  # [..., S, D/2]
    cos = jnp.cos(ang)[..., None, :]                        # [..., S, 1, D/2]
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin,
                           x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (training/prefill): exact-FLOP blocked causal flash.
# Only lower-triangular (q-block, k-block) pairs are visited, so HLO FLOPs
# match S^2/2 and peak memory is O(block_q * block_k) per step.
# ---------------------------------------------------------------------------

def _gqa_scores(q, k):
    """q: [B, KH, R, T, D]; k: [B, KH, S, D] -> [B, KH, R, T, S]."""
    return jnp.einsum("bkrtd,bksd->bkrts", q, k)


def _causal_pairs(nq: int, nk: int, block_q: int, block_k: int,
                  causal: bool):
    pairs = [(i, j) for i in range(nq) for j in range(nk)
             if (not causal) or (j * block_k < (i + 1) * block_q)]
    return (jnp.asarray([p[0] for p in pairs], jnp.int32),
            jnp.asarray([p[1] for p in pairs], jnp.int32))


def _block_mask(qi, kj, block_q, block_k, sk, causal, q_off=0):
    kg = kj * block_k + jnp.arange(block_k)
    kv_valid = kg < sk                                 # mask padded keys
    if causal:
        qg = (jnp.asarray(q_off, jnp.float32)
              + qi * block_q + jnp.arange(block_q))
        return (qg[:, None] >= kg[None, :].astype(jnp.float32)) \
            & kv_valid[None, :]
    return jnp.broadcast_to(kv_valid[None, :], (block_q, block_k))


def _flash_fwd_impl(qb, kb, vb, q_off, causal, block_q, block_k, sk,
                    unroll=False, full_pairs=False):
    """qb: [B,KH,R,NQ,Tq,D]; kb/vb: [B,KH,NK,Tk,D*]. Returns (o, lse) with
    o: [B,KH,R,NQ,Tq,Dv], lse: [B,KH,R,NQ,Tq] (+inf on fully-masked rows)."""
    b, kh, r, nq, block_q_, d = qb.shape
    nk = kb.shape[2]
    dv = vb.shape[-1]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    # sequence-parallel shards have a *traced* q offset: the causal pair
    # set cannot be enumerated statically, so visit all pairs and let the
    # mask cut (uniform SPMD program; ~2x attention FLOPs, traded for the
    # removal of per-block resharding collectives — see EXPERIMENTS §Perf)
    qi_arr, kj_arr = _causal_pairs(nq, nk, block_q, block_k,
                                   causal and not full_pairs)

    m0 = jnp.full((nq, b, kh, r, block_q), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((nq, b, kh, r, block_q), jnp.float32)
    o0 = jnp.zeros((nq, b, kh, r, block_q, dv), jnp.float32)

    def body(carry, idx):
        m, l, o = carry
        qi, kj = idx
        qblk = jax.lax.dynamic_index_in_dim(qb, qi, axis=3, keepdims=False)
        kblk = jax.lax.dynamic_index_in_dim(kb, kj, axis=2, keepdims=False)
        vblk = jax.lax.dynamic_index_in_dim(vb, kj, axis=2, keepdims=False)
        sco = _gqa_scores(qblk, kblk) * scale          # [B,KH,R,Tq,Tk]
        mask = _block_mask(qi, kj, block_q, block_k, sk, causal, q_off)
        sco = jnp.where(mask, sco, -jnp.inf)
        mi = jax.lax.dynamic_index_in_dim(m, qi, 0, keepdims=False)
        li = jax.lax.dynamic_index_in_dim(l, qi, 0, keepdims=False)
        oi = jax.lax.dynamic_index_in_dim(o, qi, 0, keepdims=False)
        mnew = jnp.maximum(mi, jnp.max(sco, axis=-1))
        msafe = jnp.where(jnp.isinf(mnew), 0.0, mnew)  # -inf rows guard
        p = jnp.exp(sco - msafe[..., None])
        p = jnp.where(jnp.isinf(sco), 0.0, p)
        corr = jnp.exp(jnp.where(jnp.isinf(mi), -jnp.inf, mi) - msafe)
        corr = jnp.where(jnp.isinf(mi), 0.0, corr)
        lnew = li * corr + jnp.sum(p, axis=-1)
        onew = oi * corr[..., None] + jnp.einsum("bkrts,bksd->bkrtd", p, vblk)
        m = jax.lax.dynamic_update_index_in_dim(m, mnew, qi, 0)
        l = jax.lax.dynamic_update_index_in_dim(l, lnew, qi, 0)
        o = jax.lax.dynamic_update_index_in_dim(o, onew, qi, 0)
        return (m, l, o), None

    (m, l, o), _ = jax.lax.scan(body, (m0, l0, o0), (qi_arr, kj_arr),
                                unroll=len(qi_arr) if unroll else 1)
    o = o / jnp.maximum(l[..., None], 1e-30)
    lse = jnp.where(l > 0, jnp.where(jnp.isinf(m), 0.0, m) + jnp.log(
        jnp.maximum(l, 1e-30)), jnp.inf)
    # -> [B,KH,R,NQ,Tq,(Dv)]
    return (o.transpose(1, 2, 3, 0, 4, 5), lse.transpose(1, 2, 3, 0, 4))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash_core(qb, kb, vb, q_off, causal, block_q, block_k, sk,
                unroll=False, full_pairs=False):
    o, _ = _flash_fwd_impl(qb, kb, vb, q_off, causal, block_q, block_k, sk,
                           unroll, full_pairs)
    return o


def _flash_core_fwd(qb, kb, vb, q_off, causal, block_q, block_k, sk,
                    unroll=False, full_pairs=False):
    o, lse = _flash_fwd_impl(qb, kb, vb, q_off, causal, block_q, block_k,
                             sk, unroll, full_pairs)
    return o, (qb, kb, vb, q_off, o, lse)


def _flash_core_bwd(causal, block_q, block_k, sk, unroll, full_pairs,
                    res, do):
    """FlashAttention-style recompute backward: no per-step AD residuals."""
    qb, kb, vb, q_off, o, lse = res
    b, kh, r, nq, bq, d = qb.shape
    nk = kb.shape[2]
    dv = vb.shape[-1]
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    qi_arr, kj_arr = _causal_pairs(nq, nk, block_q, block_k,
                                   causal and not full_pairs)
    delta = jnp.sum(do * o, axis=-1)                   # [B,KH,R,NQ,Tq]

    dq0 = jnp.zeros_like(qb)
    dk0 = jnp.zeros((b, kh, nk, block_k, d), jnp.float32)
    dv0 = jnp.zeros((b, kh, nk, block_k, dv), jnp.float32)

    def body(carry, idx):
        dq, dk, dvv = carry
        qi, kj = idx
        qblk = jax.lax.dynamic_index_in_dim(qb, qi, axis=3, keepdims=False)
        kblk = jax.lax.dynamic_index_in_dim(kb, kj, axis=2, keepdims=False)
        vblk = jax.lax.dynamic_index_in_dim(vb, kj, axis=2, keepdims=False)
        do_i = jax.lax.dynamic_index_in_dim(do, qi, axis=3, keepdims=False)
        lse_i = jax.lax.dynamic_index_in_dim(lse, qi, axis=3, keepdims=False)
        dl_i = jax.lax.dynamic_index_in_dim(delta, qi, axis=3, keepdims=False)
        sco = _gqa_scores(qblk, kblk) * scale
        mask = _block_mask(qi, kj, block_q, block_k, sk, causal, q_off)
        lse_safe = jnp.where(jnp.isinf(lse_i), 0.0, lse_i)
        p = jnp.exp(sco - lse_safe[..., None])
        p = jnp.where(mask & ~jnp.isinf(lse_i)[..., None], p, 0.0)
        # dv_j += p^T do_i ; dp = do_i v_j^T ; ds = p (dp - delta_i) scale
        dv_j = jnp.einsum("bkrts,bkrtd->bksd", p, do_i)
        dp = jnp.einsum("bkrtd,bksd->bkrts", do_i, vblk)
        ds = p * (dp - dl_i[..., None]) * scale
        dq_i = jnp.einsum("bkrts,bksd->bkrtd", ds, kblk)
        dk_j = jnp.einsum("bkrts,bkrtd->bksd", ds, qblk)
        old_q = jax.lax.dynamic_index_in_dim(dq, qi, axis=3, keepdims=False)
        dq = jax.lax.dynamic_update_index_in_dim(dq, old_q + dq_i, qi, 3)
        old_k = jax.lax.dynamic_index_in_dim(dk, kj, axis=2, keepdims=False)
        dk = jax.lax.dynamic_update_index_in_dim(dk, old_k + dk_j, kj, 2)
        old_v = jax.lax.dynamic_index_in_dim(dvv, kj, axis=2, keepdims=False)
        dvv = jax.lax.dynamic_update_index_in_dim(dvv, old_v + dv_j, kj, 2)
        return (dq, dk, dvv), None

    (dq, dk, dvv), _ = jax.lax.scan(body, (dq0, dk0, dv0),
                                    (qi_arr, kj_arr),
                                    unroll=len(qi_arr) if unroll else 1)
    return dq, dk, dvv, jnp.zeros((), jnp.float32)


_flash_core.defvjp(_flash_core_fwd, _flash_core_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    *, causal: bool = True, block_q: int = 512,
                    block_k: int = 512, unroll: bool = False,
                    q_offset=0) -> jnp.ndarray:
    """q: [B, Sq, H, D]; k, v: [B, Sk, KH, D(v)]; H % KH == 0.
    Returns [B, Sq, H, Dv].

    Blocked online-softmax over statically enumerated causal block pairs
    (exact FLOPs — upper-triangular blocks are never visited) with a
    FlashAttention-style custom VJP (recompute backward; O(block^2) AD
    memory instead of O(steps x S x D) scan residuals).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    kh = k.shape[2]
    dv = v.shape[-1]
    r = h // kh
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    pq = (-sq) % block_q
    pk = (-sk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    s, skp = sq + pq, sk + pk
    nq, nk = s // block_q, skp // block_k

    qh = q.reshape(b, s, kh, r, d).transpose(0, 2, 3, 1, 4).astype(jnp.float32)
    qb = qh.reshape(b, kh, r, nq, block_q, d)
    kb = k.transpose(0, 2, 1, 3).astype(jnp.float32).reshape(
        b, kh, nk, block_k, d)
    vb = v.transpose(0, 2, 1, 3).astype(jnp.float32).reshape(
        b, kh, nk, block_k, dv)

    static_off = isinstance(q_offset, (int, np.integer))
    q_off = jnp.asarray(q_offset, jnp.float32)
    o = _flash_core(qb, kb, vb, q_off, causal, block_q, block_k, sk,
                    unroll, full_pairs=not static_off)
    # [B,KH,R,NQ,Tq,Dv] -> [B, S, H, Dv]
    o = o.transpose(0, 3, 4, 1, 2, 5).reshape(b, s, h, dv)
    return o[:, :sq].astype(q.dtype)


def quantize_kv(x: jnp.ndarray):
    """[B, 1, KH, D] -> (int8 codes, f32 scale [B, 1, KH]) per token+head."""
    amax = jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1),
                       1e-6)
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def _query_lengths(length: jnp.ndarray, b: int, t: int) -> jnp.ndarray:
    """Broadcast a [] / [B] / [B, T] valid-prefix spec to [B, T]."""
    l = jnp.asarray(length)
    if l.ndim == 1:
        l = l[:, None]
    return jnp.broadcast_to(l, (b, t))


def staircase_mask(length: jnp.ndarray, b: int, t: int, s: int) -> jnp.ndarray:
    """[B, T, S] validity: cache position s is visible to query (b, t) iff
    s < lq[b, t]. The SINGLE definition of the multi-token staircase
    (T = K+1 speculative verify causality; T = 1 degenerates to a plain
    prefix mask) — shared by :func:`decode_attention`,
    :func:`decode_attention_int8` and the paged-attention kernel oracle
    (`kernels/ref.py:paged_attention_ref`)."""
    lq = _query_lengths(length, b, t)
    return jnp.arange(s)[None, None, :] < lq[..., None]


def ancestor_mask(length: jnp.ndarray, anc: Optional[jnp.ndarray],
                  base: Optional[jnp.ndarray], window: int,
                  b: int, t: int, s: int) -> jnp.ndarray:
    """[B, T, S] tree-attention validity — the token-tree generalization of
    :func:`staircase_mask` (which stays the chain special case).

    A speculative token *tree* is fed as one flat block of ``window``
    tokens written at cache positions ``base .. base + window - 1`` (BFS
    order). Query (b, t) sees cache position s iff s < length[b, t] AND,
    when s falls inside the fed window, bit ``s - base[b]`` of the
    query's ancestor bitmap ``anc[b, t]`` is set (the bitmap holds the
    query's root-to-self path, so siblings/uncles in the block stay
    invisible). ``anc is None`` degenerates to the staircase. Shared by
    both jnp decode attentions, the Pallas paged kernel's mask and its
    oracle (`kernels/ref.py:tree_attention_ref`)."""
    m = staircase_mask(length, b, t, s)
    if anc is None:
        return m
    fed = (jnp.arange(s, dtype=jnp.int32)[None, None, :]
           - base.astype(jnp.int32)[:, None, None])           # [B, 1, S]
    in_win = (fed >= 0) & (fed < window)
    bits = (anc.astype(jnp.int32)[:, :, None]
            >> jnp.clip(fed, 0, 31)) & 1                       # [B, T, S]
    return m & (~in_win | (bits == 1))


def decode_attention_int8(q: jnp.ndarray, k_cache: jnp.ndarray,
                          k_scale: jnp.ndarray, v_cache: jnp.ndarray,
                          v_scale: jnp.ndarray,
                          length: jnp.ndarray,
                          anc: Optional[jnp.ndarray] = None,
                          anc_base: Optional[jnp.ndarray] = None,
                          anc_window: int = 0) -> jnp.ndarray:
    """int8 KV-cache attention (beyond-paper GQSA extension: at 32k-context
    decode the cache, not the weights, dominates HBM traffic).

    q: [B, T, H, D] (T=1 decode; T=K+1 speculative verify); k/v_cache: int8
    [B, S, KH, D]; scales: f32 [B, S, KH]; length: [] / [B] / [B, T]
    per-query valid prefix (T > 1 is causal via a staircase length).
    ``anc``/``anc_base``/``anc_window``: optional tree-attention ancestor
    bitmaps (see :func:`ancestor_mask`) for token-tree verification.
    q is quantized per-head to int8 so the score contraction is an
    int8 x int8 -> int32 dot (half the cache read bytes of bf16); the
    softmax weights are likewise quantized so p @ v runs int8 x int8.
    """
    b, s, khn, d = k_cache.shape
    t, h = q.shape[1], q.shape[2]
    r = h // khn
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    qh = q.reshape(b, t, khn, r, d)
    q_i8, q_sc = quantize_kv(qh.reshape(b, t, khn * r, d))
    q_i8 = q_i8.reshape(b, t, khn, r, d)
    q_sc = q_sc.reshape(b, t, khn, r)
    sco_i = jnp.einsum("btkrd,bskd->bkrts", q_i8, k_cache,
                       preferred_element_type=jnp.int32)
    sco = (sco_i.astype(jnp.float32)
           * q_sc.transpose(0, 2, 3, 1)[..., None]
           * k_scale.transpose(0, 2, 1)[:, :, None, None, :]
           * scale)
    valid = ancestor_mask(length, anc, anc_base, anc_window,
                          b, t, s)                         # [B, T, S]
    sco = jnp.where(valid[:, None, None, :, :], sco, -jnp.inf)
    p = jax.nn.softmax(sco, axis=-1)                       # [B,KH,R,T,S]
    # fold the per-position value scale into p, then quantize p to int8
    p_scaled = p * v_scale.transpose(0, 2, 1)[:, :, None, None, :]
    p_amax = jnp.maximum(jnp.max(p_scaled, axis=-1), 1e-9)
    p_i8 = jnp.clip(jnp.round(p_scaled / p_amax[..., None] * 127.0),
                    -127, 127).astype(jnp.int8)
    o_i = jnp.einsum("bkrts,bskd->btkrd", p_i8, v_cache,
                     preferred_element_type=jnp.int32)
    o = o_i.astype(jnp.float32) * (p_amax.transpose(0, 3, 1, 2)[..., None]
                                   / 127.0)
    return o.reshape(b, t, h, d).astype(q.dtype)


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, length: jnp.ndarray,
                     anc: Optional[jnp.ndarray] = None,
                     anc_base: Optional[jnp.ndarray] = None,
                     anc_window: int = 0) -> jnp.ndarray:
    """Short-query attention against a cache.

    q: [B, T, H, D] (T=1 plain decode; T=K+1 for the speculative verify
    step's short-prefill); caches: [B, S, KH, D]; length: [] / [B] / [B, T]
    valid prefix per query (a per-query staircase makes T > 1 causal);
    ``anc``/``anc_base``/``anc_window``: optional token-tree ancestor
    bitmaps (see :func:`ancestor_mask`).
    """
    b, s, khn, d = k_cache.shape
    dv = v_cache.shape[-1]
    t, h = q.shape[1], q.shape[2]
    r = h // khn
    scale = 1.0 / jnp.sqrt(d).astype(jnp.float32)
    # keep caches in their storage dtype AND layout: no f32 copy, no
    # transpose of the whole KV history — contract in cache layout and
    # accumulate in f32 via the dot itself
    qh = q.reshape(b, t, khn, r, d).astype(k_cache.dtype)
    sco = jnp.einsum("btkrd,bskd->bkrts", qh, k_cache,
                     preferred_element_type=jnp.float32) * scale
    valid = ancestor_mask(length, anc, anc_base, anc_window,
                          b, t, s)                         # [B, T, S]
    sco = jnp.where(valid[:, None, None, :, :], sco, -jnp.inf)
    p = jax.nn.softmax(sco, axis=-1)                       # [B,KH,R,T,S]
    o = jnp.einsum("bkrts,bskd->btkrd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, t, h, dv).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------

def attn_init(rng, cfg, dtype=jnp.float32) -> Dict:
    d, h, khn, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(rng, 4)
    p = {"wq": linear_init(ks[0], h * hd, d, dtype),
         "wk": linear_init(ks[1], khn * hd, d, dtype),
         "wv": linear_init(ks[2], khn * hd, d, dtype),
         "wo": linear_init(ks[3], d, h * hd, dtype)}
    if cfg.qk_norm:
        p["q_norm"] = norm_init(hd, dtype)
        p["k_norm"] = norm_init(hd, dtype)
    return p


def attn_qkv(p: Dict, x: jnp.ndarray, positions: jnp.ndarray, cfg,
             use_pallas=False):
    b, s, d = x.shape
    h, khn, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = apply_linear(p["wq"], x, use_pallas=use_pallas).reshape(b, s, h, hd)
    k = apply_linear(p["wk"], x, use_pallas=use_pallas).reshape(b, s, khn, hd)
    v = apply_linear(p["wv"], x, use_pallas=use_pallas).reshape(b, s, khn, hd)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def attention_block(p: Dict, x: jnp.ndarray, positions: jnp.ndarray, cfg,
                    *, causal: bool = True, use_pallas=False,
                    dist=None) -> jnp.ndarray:
    """Full-sequence attention (train / prefill)."""
    if dist is not None and getattr(dist, "sp_attention", False) \
            and dist.mesh is not None \
            and x.shape[1] % dist.axis_size(dist.model_axis) == 0:
        return attention_block_sp(p, x, cfg, causal=causal,
                                  use_pallas=use_pallas, dist=dist)
    b, s, d = x.shape
    q, k, v = attn_qkv(p, x, positions, cfg, use_pallas)
    o = flash_attention(q, k, v, causal=causal,
                        block_q=cfg.attn_block_q, block_k=cfg.attn_block_k,
                        unroll=cfg.analysis_unroll)
    return apply_linear(p["wo"], o.reshape(b, s, -1), use_pallas=use_pallas)


def attention_block_sp(p: Dict, x: jnp.ndarray, cfg, *, causal=True,
                       use_pallas=False, dist=None) -> jnp.ndarray:
    """Sequence-parallel attention (shard_map over the model axis).

    Queries are sequence-sharded over `model`; the (small, GQA) K/V are
    all-gathered per shard. Head-count alignment with the TP degree becomes
    irrelevant — this removes the per-block resharding collectives GSPMD
    inserts when heads % tp != 0 (yi-34b: 56 heads, kv=8 on 16-way TP).
    Causality across shards is handled by a traced q_offset in the flash
    mask (uniform SPMD program; ~2x attention FLOPs upper bound).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    b, s, d = x.shape
    maxis = dist.model_axis
    nsh = dist.axis_size(maxis)
    s_loc = s // nsh
    dp = dist.batch_axes

    def local(xl, pp):
        i = jax.lax.axis_index(maxis)
        offset = (i * s_loc).astype(jnp.float32)
        positions = (offset + jnp.arange(s_loc)[None, :]
                     ).astype(jnp.float32) * jnp.ones((xl.shape[0], 1))
        q, k_loc, v_loc = attn_qkv(pp, xl, positions, cfg, use_pallas)
        k = jax.lax.all_gather(k_loc, maxis, axis=1, tiled=True)
        v = jax.lax.all_gather(v_loc, maxis, axis=1, tiled=True)
        o = flash_attention(q, k, v, causal=causal,
                            block_q=min(cfg.attn_block_q, s_loc),
                            block_k=cfg.attn_block_k,
                            unroll=cfg.analysis_unroll, q_offset=offset)
        yl = apply_linear(pp["wo"], o.reshape(xl.shape[0], s_loc, -1),
                          use_pallas=use_pallas)
        return yl

    pspec = jax.tree_util.tree_map(
        lambda l: P(*([None] * l.ndim)), p)
    return shard_map(local, mesh=dist.mesh,
                     in_specs=(P(dp, maxis, None), pspec),
                     out_specs=P(dp, maxis, None),
                     check_vma=False)(x, p)


def attention_decode(p: Dict, x: jnp.ndarray, cache: Dict, pos: jnp.ndarray,
                     cfg, use_pallas=False) -> Tuple[jnp.ndarray, Dict]:
    """x: [B, 1, d]; cache: {k: [B, S, KH, D], v: ...} (+k_scale/v_scale for
    the int8 cache); pos: [] shared step index or [B] per-slot positions
    (continuous batching: every slot decodes at its own depth)."""
    b = x.shape[0]
    h, khn, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    per_slot = jnp.ndim(pos) == 1
    if per_slot:
        positions = pos[:, None].astype(jnp.int32)
        slot = jnp.arange(b)

        def write3(buf, new):            # [B, S, ...] <- [B, 1, ...]
            return buf.at[slot, pos].set(new[:, 0].astype(buf.dtype))
    else:
        positions = jnp.full((b, 1), pos, jnp.int32)

        def write3(buf, new):
            start = (0, pos) + (0,) * (buf.ndim - 2)
            return jax.lax.dynamic_update_slice(
                buf, new.astype(buf.dtype), start)
    q, k, v = attn_qkv(p, x, positions, cfg, use_pallas)
    if "k_scale" in cache:   # int8 KV cache
        k_i8, k_sc = quantize_kv(k)
        v_i8, v_sc = quantize_kv(v)
        k_cache = write3(cache["k"], k_i8)
        v_cache = write3(cache["v"], v_i8)
        k_scale = write3(cache["k_scale"], k_sc)
        v_scale = write3(cache["v_scale"], v_sc)
        if use_pallas and not per_slot:
            from repro.kernels import ops as kops
            r = h // khn
            o = kops.kv_decode_attention(
                q.reshape(b, khn, r, hd), k_cache, k_scale,
                v_cache, v_scale, pos + 1)
            o = o.reshape(b, 1, h, hd).astype(x.dtype)
        else:
            o = decode_attention_int8(q, k_cache, k_scale, v_cache,
                                      v_scale, pos + 1)
        y = apply_linear(p["wo"], o.reshape(b, 1, -1), use_pallas=use_pallas)
        return y, {"k": k_cache, "v": v_cache, "k_scale": k_scale,
                   "v_scale": v_scale}
    k_cache = write3(cache["k"], k)
    v_cache = write3(cache["v"], v)
    o = decode_attention(q, k_cache, v_cache, pos + 1)
    y = apply_linear(p["wo"], o.reshape(b, 1, -1), use_pallas=use_pallas)
    return y, {"k": k_cache, "v": v_cache}


def paged_block_geometry(positions: jnp.ndarray, t: int,
                         tree: Optional[Dict]):
    """Position/mask plumbing shared by every paged decode block
    (:func:`attention_decode_paged` and `models/mla.py:mla_decode_paged`).

    ``positions`` [B] is the write position of each slot's FIRST fed
    token (token t lands at positions + t). Returns ``(pos_bt [B, T]
    write positions, rope_pos [B, T] RoPE positions, length [B, T]
    per-query valid prefix, base [B] | None, anc [B, T] | None,
    window int)`` — the chain staircase when ``tree`` is None, else the
    token-tree semantics of DESIGN.md §8 (RoPE at tree DEPTH, ancestor
    bitmaps over the fed window, storage still slot-sequential).
    """
    b = positions.shape[0]
    pos_bt = (positions[:, None].astype(jnp.int32)
              + jnp.arange(t, dtype=jnp.int32)[None, :])     # write slots
    if tree is not None:
        window = int(tree["window"])
        base = positions.astype(jnp.int32) - jnp.int32(tree["start"])
        rope_pos = base[:, None] + tree["depths"][None, :].astype(jnp.int32)
        length = jnp.broadcast_to((base + window)[:, None], (b, t))
        anc = jnp.broadcast_to(
            tree["anc"][None, :].astype(jnp.int32), (b, t))
    else:
        window = 0
        base = anc = None
        rope_pos = pos_bt
        length = pos_bt + 1                                  # [B, T]
    return pos_bt, rope_pos, length, base, anc, window


def page_write(pages: jnp.ndarray, page: jnp.ndarray, off: jnp.ndarray,
               new: jnp.ndarray) -> jnp.ndarray:
    """Scatter per-token rows into a head-major pool: pages [KH, P, ps, X]
    <- new [B, T, KH, X] at (page, off) [B, T]. Rows whose page is the
    out-of-range sentinel (>= P) are dropped."""
    return pages.at[:, page, off].set(
        jnp.moveaxis(new, 2, 0).astype(pages.dtype), mode="drop")


def paged_write_pages(block_tables: jnp.ndarray, pos_bt: jnp.ndarray,
                      page_size: int, num_pages: int,
                      feed_len: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Page id of each fed token's write position [B, T]. With a ragged
    ``feed_len`` [B] (prefix-cache tail prefill, DESIGN.md §13), rows
    feed feed_len[i] <= T real tokens: positions at or past it map to
    the out-of-range sentinel so their writes drop — the convention
    batched prefill uses for padding — instead of take_along_axis
    clipping them onto the row's last live page and corrupting it."""
    page = jnp.take_along_axis(block_tables, pos_bt // page_size, axis=1)
    if feed_len is None:
        return page
    t = pos_bt.shape[1]
    return jnp.where(
        jnp.arange(t, dtype=jnp.int32)[None, :] < feed_len[:, None],
        page, num_pages)


def attention_decode_paged(p: Dict, x: jnp.ndarray, cache: Dict,
                           block_tables: jnp.ndarray, positions: jnp.ndarray,
                           cfg, use_pallas=False, tree: Optional[Dict] = None,
                           feed_len: Optional[jnp.ndarray] = None
                           ) -> Tuple[jnp.ndarray, Dict]:
    """One decode step of T tokens against a *paged* KV cache (one layer's
    view). T=1 is plain continuous-batching decode; T=K+1 is the
    speculative-decoding verify step's per-slot short-prefill; a token
    TREE block (``tree`` set) is the tree-speculative draft/verify path.

    x: [B, T, d]; positions: [B] write position of each slot's FIRST
    token (token t lands at positions + t); block_tables: [B, MP] page ids
    (entries == n_pages are out-of-range sentinels: scatter-writes to
    them are dropped, gather-reads clip and get masked by the per-query
    length). cache: {"k_pages"/"v_pages": [KH, P, ps, D]} head-major
    (+ "k_scale_pages"/"v_scale_pages" [KH, P, ps, 1] for int8).

    Causality inside the T block comes from the per-query staircase
    length (query t sees cache positions < positions + t + 1); the K/V of
    all T tokens are scattered before the attention reads them, so later
    queries attend to earlier fed tokens exactly as a sequential decode
    would.

    ``tree`` switches the block to token-tree semantics
    (engine/spec/tree.py, DESIGN.md §8): the T fed tokens are a slice of
    a flat BFS tree block of ``tree["window"]`` tokens whose root sits at
    cache position ``positions - tree["start"]``. Storage stays
    slot-sequential (token t still writes at positions + t) but RoPE runs
    at the token's tree DEPTH (``tree["depths"]`` [T]) and the mask is
    the per-query ancestor bitmap ``tree["anc"]`` [T] over the window
    (:func:`ancestor_mask`) — so a node's K/V is rotated for the position
    it would hold in sequential decode, and the accepted path can be
    compacted by pure slot moves, no re-rotation.

    With ``use_pallas`` the attention runs the fused paged kernel
    (`kernels/paged_attention.py`): it streams each slot's live pages
    through VMEM directly — the dense `[B, MP*ps, ...]` page gather
    below exists only on the jnp reference path, and even there the
    engine clamps ``block_tables`` to the batch's max *occupied* page
    count before calling in (``decode_step``'s ``max_live_pages``), so
    the reference never pays for unallocated pages either.
    """
    from repro.kernels import ops as kops
    from repro.kernels.ref import page_view
    b, t, _ = x.shape
    kp = cache["k_pages"]
    page_size = kp.shape[2]
    pos_bt, rope_pos, length, base, anc, window = paged_block_geometry(
        positions, t, tree)
    q, k, v = attn_qkv(p, x, rope_pos, cfg, use_pallas)
    page = paged_write_pages(block_tables, pos_bt, page_size, kp.shape[1],
                             feed_len)
    off = pos_bt % page_size

    def view(buf):                       # pool -> [B, MP*ps, KH, X]
        return page_view(buf, block_tables)

    if "k_scale_pages" in cache:         # int8 paged cache
        k_i8, k_sc = quantize_kv(k)
        v_i8, v_sc = quantize_kv(v)
        new = {"k_pages": page_write(kp, page, off, k_i8),
               "v_pages": page_write(cache["v_pages"], page, off, v_i8),
               "k_scale_pages": page_write(cache["k_scale_pages"], page,
                                           off, k_sc[..., None]),
               "v_scale_pages": page_write(cache["v_scale_pages"], page,
                                           off, v_sc[..., None])}
        if use_pallas:
            o = kops.paged_decode_attention(
                q, new["k_pages"], new["v_pages"], length, block_tables,
                new["k_scale_pages"], new["v_scale_pages"],
                anc=anc, anc_base=base,
                anc_window=window).astype(q.dtype)
        else:
            o = decode_attention_int8(q, view(new["k_pages"]),
                                      view(new["k_scale_pages"])[..., 0],
                                      view(new["v_pages"]),
                                      view(new["v_scale_pages"])[..., 0],
                                      length, anc, base, window)
    else:
        new = {"k_pages": page_write(kp, page, off, k),
               "v_pages": page_write(cache["v_pages"], page, off, v)}
        if use_pallas:
            o = kops.paged_decode_attention(
                q, new["k_pages"], new["v_pages"], length,
                block_tables, anc=anc, anc_base=base,
                anc_window=window).astype(q.dtype)
        else:
            o = decode_attention(q, view(new["k_pages"]),
                                 view(new["v_pages"]), length,
                                 anc, base, window)
    y = apply_linear(p["wo"], o.reshape(b, t, -1), use_pallas=use_pallas)
    return y, new


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def mlp_init(rng, d: int, d_ff: int, mlp_type: str, dtype=jnp.float32) -> Dict:
    ks = jax.random.split(rng, 3)
    if mlp_type == "swiglu":
        return {"wg": linear_init(ks[0], d_ff, d, dtype),
                "wu": linear_init(ks[1], d_ff, d, dtype),
                "wd": linear_init(ks[2], d, d_ff, dtype)}
    return {"wu": linear_init(ks[0], d_ff, d, dtype),
            "wd": linear_init(ks[1], d, d_ff, dtype)}


def mlp_block(p: Dict, x: jnp.ndarray, mlp_type: str,
              use_pallas=False) -> jnp.ndarray:
    if mlp_type == "swiglu":
        g = apply_linear(p["wg"], x, use_pallas=use_pallas)
        u = apply_linear(p["wu"], x, use_pallas=use_pallas)
        return apply_linear(p["wd"], jax.nn.silu(g) * u,
                            use_pallas=use_pallas)
    u = apply_linear(p["wu"], x, use_pallas=use_pallas)
    return apply_linear(p["wd"], jax.nn.gelu(u), use_pallas=use_pallas)
