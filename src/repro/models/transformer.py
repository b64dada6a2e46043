"""Decoder-only LM: dense / MoE / MLA-MoE / VLM families.

Layers are weight-stacked and iterated with lax.scan (small HLO, fast
compiles at 60+ layers); the per-layer body is remat'd when cfg.remat.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.gqs_layer import apply_linear
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _layer_init(rng, cfg, dtype):
    ks = jax.random.split(rng, 4)
    p = {"ln1": L.norm_init(cfg.d_model, dtype),
         "ln2": L.norm_init(cfg.d_model, dtype)}
    if cfg.family == "mla_moe":
        p["attn"] = MLA.mla_init(ks[0], cfg, dtype)
    else:
        p["attn"] = L.attn_init(ks[0], cfg, dtype)
    if cfg.moe is not None:
        p["moe"] = MOE.moe_init(ks[1], cfg, dtype)
    else:
        p["mlp"] = L.mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.mlp_type,
                              dtype)
    return p


def init_params(rng, cfg) -> Dict:
    dtype = cfg.params_dtype
    k_embed, k_layers, k_head = jax.random.split(rng, 3)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    params = {
        "embed": jax.random.normal(k_embed, (cfg.vocab, cfg.d_model),
                                   dtype) * 0.02,
        "layers": jax.vmap(lambda k: _layer_init(k, cfg, dtype))(layer_keys),
        "final_norm": L.norm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.linear_init(k_head, cfg.vocab, cfg.d_model,
                                          dtype, scale=0.02)
    return params


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def _block(lp: Dict, h: jnp.ndarray, positions: jnp.ndarray, cfg, dist,
           use_pallas) -> Tuple[jnp.ndarray, jnp.ndarray]:
    if cfg.family == "mla_moe":
        a = MLA.mla_block(lp["attn"], L.rmsnorm(h, lp["ln1"], cfg.norm_eps),
                          positions, cfg, use_pallas)
    else:
        a = L.attention_block(lp["attn"],
                              L.rmsnorm(h, lp["ln1"], cfg.norm_eps),
                              positions, cfg, use_pallas=use_pallas,
                              dist=dist)
    h = h + a
    hn = L.rmsnorm(h, lp["ln2"], cfg.norm_eps)
    if cfg.moe is not None:
        m, aux = MOE.moe_block(lp["moe"], hn, cfg, dist, use_pallas)
    else:
        m, aux = L.mlp_block(lp["mlp"], hn, cfg.mlp_type, use_pallas), 0.0
    return h + m, jnp.asarray(aux, jnp.float32)


def embed_tokens(params: Dict, tokens: jnp.ndarray, cfg) -> jnp.ndarray:
    return jnp.take(params["embed"], tokens, axis=0).astype(cfg.compute_dtype)


def unembed(params: Dict, h: jnp.ndarray, cfg) -> jnp.ndarray:
    h = L.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    if cfg.tie_embeddings:
        return jnp.einsum("bsd,vd->bsv", h,
                          params["embed"].astype(h.dtype))
    return apply_linear(params["lm_head"], h)


def forward(params: Dict, tokens: jnp.ndarray, cfg, dist=None,
            use_pallas: bool = False,
            patch_embeds: Optional[jnp.ndarray] = None,
            last_only: bool = False
            ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens: [B, S_text]. Returns (logits [B, S, V], aux loss scalar).

    VLM: ``patch_embeds`` [B, P, d] are prepended to the token embeddings
    (the assignment's modality-frontend stub); S = P + S_text.
    """
    h = embed_tokens(params, tokens, cfg)
    if patch_embeds is not None:
        h = jnp.concatenate([patch_embeds.astype(h.dtype), h], axis=1)
    b, s, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    # sequence-parallel residual stream (Megatron-SP): keep h sharded on
    # (batch, seq@model); per-token ops run local, TP matmuls turn their
    # activation all-reduces into reduce-scatter/all-gather pairs (2x fewer
    # bytes). Enabled together with SP attention.
    if dist is not None and getattr(dist, "sp_attention", False) \
            and s % dist.axis_size(dist.model_axis) == 0:
        res_spec = __import__("jax").sharding.PartitionSpec(
            dist.batch_axes, dist.model_axis, None)
    elif dist is not None:
        res_spec = dist.batch_spec(3)
    else:
        res_spec = None
    if dist is not None:
        h = dist.constrain(h, res_spec)

    def body(carry, lp):
        hh, aux = carry
        hh, aux_l = _block(lp, hh, positions, cfg, dist, use_pallas)
        if dist is not None:
            hh = dist.constrain(hh, res_spec)
        return (hh, aux + aux_l), None

    if cfg.remat:
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    (h, aux), _ = jax.lax.scan(body, (h, jnp.float32(0.0)), params["layers"])
    if last_only:
        h = h[:, -1:, :]
    logits = unembed(params, h, cfg)
    return logits, aux / cfg.n_layers


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_seq: int, dtype=None) -> Dict:
    dtype = dtype or cfg.compute_dtype
    lyr = cfg.n_layers
    if cfg.family == "mla_moe":
        m = cfg.mla
        return {"c_kv": jnp.zeros((lyr, batch, max_seq, m.kv_lora_rank),
                                  dtype),
                "k_rope": jnp.zeros((lyr, batch, max_seq, m.qk_rope_dim),
                                    dtype)}
    if cfg.kv_cache_dtype == "int8":
        kh = cfg.n_kv_heads
        return {"k": jnp.zeros((lyr, batch, max_seq, kh, cfg.hd), jnp.int8),
                "v": jnp.zeros((lyr, batch, max_seq, kh, cfg.hd), jnp.int8),
                "k_scale": jnp.zeros((lyr, batch, max_seq, kh), jnp.float32),
                "v_scale": jnp.zeros((lyr, batch, max_seq, kh), jnp.float32)}
    return {"k": jnp.zeros((lyr, batch, max_seq, cfg.n_kv_heads, cfg.hd),
                           dtype),
            "v": jnp.zeros((lyr, batch, max_seq, cfg.n_kv_heads, cfg.hd),
                           dtype)}


def init_paged_cache(cfg, num_pages: int, page_size: int,
                     dtype=None) -> Dict:
    """Paged KV pool: fixed-size pages shared by all slots via per-request
    block tables (see DESIGN.md §3). Leaves are head-major
    [L, KH, P, ps, X]: the decode scan hands each layer its
    [KH, P, ps, X] view, and the paged-attention kernel DMAs one
    (head, page) tile [ps, X] per grid step (kernels/paged_attention.py).

    ``mla_moe`` pages the LATENT cache (DESIGN.md §9): one pool of
    [L, 1, P, ps, kv_lora_rank + qk_rope_dim] rows — a single logical KV
    "head" per page, and NO V pool (values are up-projected from the
    latent through W_UV after attention). Latent pages stay in the
    compute dtype regardless of ``kv_cache_dtype`` (int8 latent pages
    are a recorded follow-on, ROADMAP). int8 pages carry per-token x head
    scales [L, KH, P, ps, 1]."""
    dtype = dtype or cfg.compute_dtype
    lyr = cfg.n_layers
    if cfg.family == "mla_moe":
        m = cfg.mla
        return {"lat_pages": jnp.zeros(
            (lyr, 1, num_pages, page_size, m.kv_lora_rank + m.qk_rope_dim),
            dtype)}
    shape = (lyr, cfg.n_kv_heads, num_pages, page_size, cfg.hd)
    if cfg.kv_cache_dtype == "int8":
        scale = shape[:-1] + (1,)
        return {"k_pages": jnp.zeros(shape, jnp.int8),
                "v_pages": jnp.zeros(shape, jnp.int8),
                "k_scale_pages": jnp.zeros(scale, jnp.float32),
                "v_scale_pages": jnp.zeros(scale, jnp.float32)}
    return {"k_pages": jnp.zeros(shape, dtype),
            "v_pages": jnp.zeros(shape, dtype)}


def prefill(params: Dict, cache: Dict, tokens: jnp.ndarray,
            lengths: jnp.ndarray, block_tables: jnp.ndarray, cfg,
            dist=None, use_pallas: bool = False
            ) -> Tuple[jnp.ndarray, Dict]:
    """True batched prefill: run the full (padded) prompts through flash
    attention ONCE and scatter every layer's K/V into the paged cache.

    tokens: [B, S] right-padded prompts; lengths: [B] valid prefix;
    block_tables: [B, MP] page ids. Padding positions map to the
    out-of-range page sentinel, so their K/V scatter-writes are dropped;
    causality keeps valid tokens from attending to the (trailing) padding.
    Returns (last-valid-token logits [B, 1, V], filled cache).
    """
    b, s = tokens.shape
    leaf = jax.tree_util.tree_leaves(cache)[0]
    num_pages, page_size = leaf.shape[2], leaf.shape[3]
    h = embed_tokens(params, tokens, cfg)
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    # (b, s) -> flat page/offset; invalid (padding) positions -> OOB page
    page = jnp.take_along_axis(
        block_tables, positions // page_size, axis=1)       # [B, S]
    page = jnp.where(positions < lengths[:, None], page, num_pages)
    off = positions % page_size
    mla = cfg.family == "mla_moe"
    int8 = "k_scale_pages" in cache

    def body(carry, xs):
        hh = carry
        lp, lc = xs
        hn = L.rmsnorm(hh, lp["ln1"], cfg.norm_eps)
        if mla:
            # full-seq latent attention; the latent row (post-norm c_kv
            # ++ post-RoPE k_rope) pages as ONE pool — no V scatter
            a, latent = MLA.mla_prefill_paged(lp["attn"], hn, positions,
                                              cfg, use_pallas)
            new_c = {"lat_pages": L.page_write(lc["lat_pages"], page, off,
                                               latent[:, :, None])}
        else:
            q, k, v = L.attn_qkv(lp["attn"], hn, positions, cfg, use_pallas)
            o = L.flash_attention(q, k, v, causal=True,
                                  block_q=cfg.attn_block_q,
                                  block_k=cfg.attn_block_k,
                                  unroll=cfg.analysis_unroll)
            a = apply_linear(lp["attn"]["wo"], o.reshape(b, s, -1),
                             use_pallas=use_pallas)
            if int8:
                k_i8, k_sc = L.quantize_kv(k)
                v_i8, v_sc = L.quantize_kv(v)
                new_c = {
                    "k_pages": L.page_write(lc["k_pages"], page, off, k_i8),
                    "v_pages": L.page_write(lc["v_pages"], page, off, v_i8),
                    "k_scale_pages": L.page_write(lc["k_scale_pages"], page,
                                                  off, k_sc[..., None]),
                    "v_scale_pages": L.page_write(lc["v_scale_pages"], page,
                                                  off, v_sc[..., None])}
            else:
                new_c = {
                    "k_pages": L.page_write(lc["k_pages"], page, off, k),
                    "v_pages": L.page_write(lc["v_pages"], page, off, v)}
        hh = hh + a
        hn = L.rmsnorm(hh, lp["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            m, _ = MOE.moe_block(lp["moe"], hn, cfg, dist, use_pallas)
        else:
            m = L.mlp_block(lp["mlp"], hn, cfg.mlp_type, use_pallas)
        return hh + m, new_c

    h, new_cache = jax.lax.scan(body, h, (params["layers"], cache))
    # logits only at each row's last valid token (cheap unembed: [B, 1, V])
    h_last = jnp.take_along_axis(
        h, jnp.maximum(lengths - 1, 0)[:, None, None], axis=1)
    logits = unembed(params, h_last, cfg)
    return logits, new_cache


def decode_step(params: Dict, cache: Dict, tokens: jnp.ndarray,
                pos: jnp.ndarray, cfg, dist=None, use_pallas: bool = False,
                block_tables=None, max_live_pages: Optional[int] = None,
                tree: Optional[Dict] = None,
                feed_len: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, Dict]:
    """tokens: [B, T]; pos: scalar shared step index OR [B] per-slot
    positions. ``cache`` is either the contiguous cache from
    :func:`init_cache` (T must be 1) or the paged view from
    :func:`init_paged_cache` (then ``block_tables`` [B, MP] is required
    and T may exceed 1: token t is written/attended at pos + t — the
    speculative-decoding verify step's per-slot short-prefill).
    ``mla_moe`` paged caches route through the absorbed latent path
    (`models/mla.py:mla_decode_paged`); everything below — staircase,
    tree, clamp — applies unchanged.

    ``tree`` (paged cache only) switches the T fed tokens to token-tree
    semantics: ``{"depths": [T], "anc": [T], "window": int, "start":
    int}`` — RoPE at tree depth, per-query ancestor-bitmap masking over
    the fed window (`models/layers.py:attention_decode_paged`,
    DESIGN.md §8).

    ``feed_len`` [B] (paged cache only) makes the T-token feed ragged:
    row i's tokens at t >= feed_len[i] are padding — their K/V writes
    are dropped (sentinel-masked) and their logits are garbage to be
    discarded by the caller. This is the prefix-cache tail prefill
    (DESIGN.md §13): slots prefill unshared tails of different lengths
    padded to one T.

    ``max_live_pages`` (static) clamps the block tables to the batch's
    max *occupied* page count: every slot's allocation (prompt + budget
    + lookahead) fits in the leading entries, so the trailing all-
    sentinel columns carry no information — dropping them shrinks the
    jnp reference's dense page gather and the Pallas kernel's grid from
    O(max_pages) to O(occupied pages). The engine buckets the value
    (pow2) so retraces stay bounded. Returns (logits [B, T, V], cache).
    """
    paged = isinstance(cache, dict) and ("k_pages" in cache
                                         or "lat_pages" in cache)
    if paged and block_tables is None:
        raise ValueError("paged cache decode requires block_tables")
    if tree is not None and not paged:
        raise ValueError("token-tree decode requires the paged cache")
    if feed_len is not None and not paged:
        raise ValueError("ragged feed_len requires the paged cache")
    if paged and max_live_pages is not None:
        block_tables = block_tables[
            :, :max(1, min(max_live_pages, block_tables.shape[1]))]
    h = embed_tokens(params, tokens, cfg)

    def body(hh, xs):
        lp, lc = xs
        hn = L.rmsnorm(hh, lp["ln1"], cfg.norm_eps)
        if paged and cfg.family == "mla_moe":
            a, new_c = MLA.mla_decode_paged(lp["attn"], hn, lc,
                                            block_tables, pos, cfg,
                                            use_pallas, tree=tree,
                                            feed_len=feed_len)
        elif paged:
            a, new_c = L.attention_decode_paged(lp["attn"], hn, lc,
                                                block_tables, pos, cfg,
                                                use_pallas, tree=tree,
                                                feed_len=feed_len)
        elif cfg.family == "mla_moe":
            a, new_c = MLA.mla_decode(lp["attn"], hn, lc, pos, cfg,
                                      use_pallas)
        else:
            a, new_c = L.attention_decode(lp["attn"], hn, lc, pos, cfg,
                                          use_pallas)
        hh = hh + a
        hn = L.rmsnorm(hh, lp["ln2"], cfg.norm_eps)
        if cfg.moe is not None:
            m, _ = MOE.moe_block(lp["moe"], hn, cfg, dist, use_pallas)
        else:
            m = L.mlp_block(lp["mlp"], hn, cfg.mlp_type, use_pallas)
        return hh + m, new_c

    h, new_cache = jax.lax.scan(body, h, (params["layers"], cache))
    logits = unembed(params, h, cfg)
    return logits, new_cache
