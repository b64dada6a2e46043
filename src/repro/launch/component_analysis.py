"""Component-wise cost analysis — exact FLOP/byte/collective accounting.

XLA's HloCostAnalysis visits a while-loop body ONCE, so cost_analysis() on a
scan-over-layers program undercounts by ~n_layers. We therefore lower +
compile each repeated component separately (with inner scans unrolled), read
its per-device cost, and combine:

    train:   n_layers x grad(block) + grad(head) + optimizer + grad-sync
    prefill: n_layers x block + head(last-token)
    decode:  n_layers x decode(block) + decode(head)

Each component is compiled on the same production mesh with the same
shardings as the full program, so TP/EP collectives inside a layer are
captured per-execution. The whole-program compile (dryrun.run_cell) remains
the source of truth for memory_analysis and for "it lowers+compiles".
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeCfg
from repro.core.gqs_layer import GQSAConfig
from repro.core.model_compress import compress_params_shapes
from repro.dist.sharding import DistContext, param_shardings
from repro.launch import hlo_analysis as H
from repro.launch.steps import make_dist
from repro.models import encdec as ED
from repro.models import hybrid as HY
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.models import ssm as SSM
from repro.models import ssm_lm as SL
from repro.models import transformer as TF
from repro.models.registry import get_model, lm_loss
from repro.optim import adamw


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _slice_layer(tree):
    """Drop the leading stack dim from every leaf (SDS-safe)."""
    return jax.tree_util.tree_map(
        lambda l: _sds(l.shape[1:], l.dtype), tree)


def _compile_component(fn, arg_sds: Tuple, arg_sh: Tuple, mesh,
                       out_sh=None):
    with mesh:
        kw = {"in_shardings": arg_sh}
        if out_sh is not None:
            kw["out_shardings"] = out_sh
        lowered = jax.jit(fn, **kw).lower(*arg_sds)
        compiled = lowered.compile()
    cost = compiled.cost_analysis() or {}
    coll = H.collective_bytes_from_hlo(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(coll.get("total", 0.0))}


def _acfg(cfg: ModelConfig, shape: ShapeCfg,
          unroll: bool = True) -> ModelConfig:
    """Analysis config. unroll=True: inner scans unrolled + wide attention
    blocks (exact FLOPs / collectives; pair count stays ~36 even at 32k).
    unroll=False: scans kept + small blocks — HloCostAnalysis then counts
    each loop body once, which approximates the HBM traffic of a *fused*
    attention/SSD kernel (block intermediates live in VMEM on TPU), so this
    pass feeds the memory roofline term."""
    if unroll:
        blk = max(512, shape.seq_len // 8)
        return dataclasses.replace(cfg, analysis_unroll=True,
                                   attn_block_q=blk, attn_block_k=blk)
    return dataclasses.replace(cfg, analysis_unroll=False,
                               attn_block_q=512, attn_block_k=512)


def _batch_sh(dist, ndim, batch, b_dim=0):
    dp = int(np.prod([dist.axis_size(a) for a in dist.batch_axes]))
    spec = [None] * ndim
    if batch % dp == 0 and batch >= dp:
        spec[b_dim] = dist.batch_axes
    return NamedSharding(dist.mesh, P(*spec))


def _h_sh(dist, batch, seq):
    """Residual-stream sharding for layer components: matches the
    whole-program constraint (seq@model when sequence-parallel)."""
    dp = int(np.prod([dist.axis_size(a) for a in dist.batch_axes]))
    spec = [None, None, None]
    if batch % dp == 0 and batch >= dp:
        spec[0] = dist.batch_axes
    if getattr(dist, "sp_attention", False) and \
            seq % dist.axis_size(dist.model_axis) == 0:
        spec[1] = dist.model_axis
    return NamedSharding(dist.mesh, P(*spec))


def _rep(dist, ndim):
    return NamedSharding(dist.mesh, P(*([None] * ndim)))


# ---------------------------------------------------------------------------
# component definitions per family
# ---------------------------------------------------------------------------

def _train_components(cfg, shape, dist, mesh, accum: int,
                      unroll: bool = True) -> List[Tuple]:
    """[(name, multiplier, fn, arg_sds, arg_sh)] for a train step."""
    api = get_model(cfg)
    acfg = _acfg(cfg, shape, unroll)
    dp = int(np.prod([dist.axis_size(a) for a in dist.batch_axes]))
    b = shape.global_batch // max(accum, 1)
    s = shape.seq_len
    dt = cfg.compute_dtype
    d = cfg.d_model
    params_sds = jax.eval_shape(
        functools.partial(api.init_params, cfg=cfg), jax.random.PRNGKey(0))
    p_sh_full = param_shardings(params_sds, dist)
    comps = []

    h_sds = _sds((b, s, d), dt)
    h_sh = _h_sh(dist, b, s)
    pos_sds = _sds((b, s), jnp.int32)

    def add_block(name, mult, block_fn, lp_key):
        lp_sds = _slice_layer(params_sds[lp_key])
        lp_sh = param_shardings(lp_sds, dist)

        def g(lp, h, positions):
            def f(lp_, h_):
                out = block_fn(lp_, h_, positions)
                return jnp.sum(out.astype(jnp.float32))
            _, grads = jax.value_and_grad(f, argnums=(0, 1))(lp, h)
            return grads
        # grads land with the PARAM shardings (ZeRO reduce-scatter over the
        # FSDP axis rather than a full all-reduce)
        comps.append((name, mult, g, (lp_sds, h_sds, pos_sds),
                      (lp_sh, h_sh, _batch_sh(dist, 2, b)),
                      (lp_sh, h_sh)))

    if cfg.family in ("dense", "moe", "mla_moe", "vlm"):
        def block_fn(lp, h, positions):
            out, aux = TF._block(lp, h, positions, acfg, dist, False)
            return out + 0 * aux
        add_block("layer_grad", cfg.n_layers * accum, block_fn, "layers")
    elif cfg.family == "ssm":
        def block_fn(lp, h, positions):
            hn = L.rmsnorm(h, lp["ln"], cfg.norm_eps)
            return h + SSM.mamba_block(lp["mamba"], hn, acfg)
        add_block("layer_grad", cfg.n_layers * accum, block_fn, "layers")
    elif cfg.family == "hybrid":
        ng, rem = HY._n_groups(cfg)
        grouped = params_sds["groups"]
        one_m = jax.tree_util.tree_map(
            lambda l: _sds(l.shape[2:], l.dtype), grouped)
        mp_sh = param_shardings(one_m, dist)

        def mamba_fn(lp, h, positions):
            return h + SSM.mamba_block(lp, h, acfg)

        def g_m(lp, h, positions):
            def f(lp_, h_):
                return jnp.sum(mamba_fn(lp_, h_, positions)
                               .astype(jnp.float32))
            return jax.value_and_grad(f, argnums=(0, 1))(lp, h)[1]
        comps.append(("mamba_grad", cfg.n_layers * accum, g_m,
                      (one_m, h_sds, pos_sds),
                      (mp_sh, h_sh, _batch_sh(dist, 2, b))))

        sp_sds = params_sds["shared"]
        sp_sh = param_shardings(sp_sds, dist)

        def g_s(sp, h, positions):
            def f(sp_, h_):
                return jnp.sum(HY._shared_block(sp_, h_, positions, acfg,
                                                False).astype(jnp.float32))
            return jax.value_and_grad(f, argnums=(0, 1))(sp, h)[1]
        comps.append(("shared_attn_grad", ng * accum, g_s,
                      (sp_sds, h_sds, pos_sds),
                      (sp_sh, h_sh, _batch_sh(dist, 2, b))))
    elif cfg.family == "encdec":
        f_sds = _sds((b, cfg.n_frames, d), dt)
        enc_l = _slice_layer(params_sds["enc_layers"])
        dec_l = _slice_layer(params_sds["dec_layers"])
        epos = _sds((b, cfg.n_frames), jnp.int32)

        def enc_fn(lp, h, positions):
            a = L.attention_block(lp["attn"],
                                  L.rmsnorm(h, lp["ln1"], cfg.norm_eps),
                                  positions, acfg, causal=False)
            h = h + a
            return h + L.mlp_block(
                lp["mlp"], L.rmsnorm(h, lp["ln2"], cfg.norm_eps),
                cfg.mlp_type)

        def g_enc(lp, h, positions):
            def f(lp_, h_):
                return jnp.sum(enc_fn(lp_, h_, positions)
                               .astype(jnp.float32))
            return jax.value_and_grad(f, argnums=(0, 1))(lp, h)[1]
        comps.append(("enc_layer_grad", cfg.enc_layers * accum, g_enc,
                      (enc_l, f_sds, epos),
                      (param_shardings(enc_l, dist), _batch_sh(dist, 3, b),
                       _batch_sh(dist, 2, b))))

        def dec_fn(lp, h, enc_out, positions):
            a = L.attention_block(lp["self_attn"],
                                  L.rmsnorm(h, lp["ln1"], cfg.norm_eps),
                                  positions, acfg)
            h = h + a
            ek, ev = ED._cross_kv(lp["cross"], enc_out, acfg, False)
            c = ED._cross_attend(lp["cross"],
                                 L.rmsnorm(h, lp["ln2"], cfg.norm_eps),
                                 ek, ev, acfg, False)
            h = h + c
            return h + L.mlp_block(
                lp["mlp"], L.rmsnorm(h, lp["ln3"], cfg.norm_eps),
                cfg.mlp_type)

        def g_dec(lp, h, enc_out, positions):
            def f(lp_, h_, e_):
                return jnp.sum(dec_fn(lp_, h_, e_, positions)
                               .astype(jnp.float32))
            return jax.value_and_grad(f, argnums=(0, 1, 2))(lp, h, enc_out)[1]
        comps.append(("dec_layer_grad", cfg.n_layers * accum, g_dec,
                      (dec_l, h_sds, f_sds, pos_sds),
                      (param_shardings(dec_l, dist), h_sh,
                       _batch_sh(dist, 3, b), _batch_sh(dist, 2, b))))

    # head: embed + final norm + unembed + loss (+ backward)
    tok_sds = _sds((b, s), jnp.int32)
    head_keys = [k for k in ("embed", "final_norm", "lm_head") if
                 k in params_sds]
    hp_sds = {k: params_sds[k] for k in head_keys}
    hp_sh = param_shardings(hp_sds, dist)

    def head_fn(hp, h_res, tokens, labels):
        h = jnp.take(hp["embed"], tokens, axis=0).astype(dt) + h_res
        if cfg.family == "encdec":
            h2 = L.rmsnorm(h, hp["final_norm"], cfg.norm_eps)
            logits = jnp.einsum("bsd,vd->bsv", h2,
                                hp["lm_head"]["w"].astype(h2.dtype)) \
                if "lm_head" in hp else None
        else:
            logits = TF.unembed(hp, h, cfg)
        return lm_loss(logits, labels)

    def g_head(hp, h_res, tokens, labels):
        return jax.value_and_grad(head_fn, argnums=(0, 1))(
            hp, h_res, tokens, labels)[1]
    comps.append(("head_grad", accum, g_head,
                  (hp_sds, h_sds, tok_sds, tok_sds),
                  (hp_sh, h_sh, _batch_sh(dist, 2, b),
                   _batch_sh(dist, 2, b))))

    # optimizer update over the full tree
    opt_sds = jax.eval_shape(adamw.init_state, params_sds)
    o_sh = {"m": p_sh_full, "v": p_sh_full, "step": _rep(dist, 0)}
    grads_sh = p_sh_full

    def opt_fn(params, grads, opt_state):
        newp, news, _ = adamw.apply_updates(params, grads, opt_state,
                                            adamw.AdamWConfig())
        return newp, news
    comps.append(("optimizer", 1, opt_fn,
                  (params_sds, params_sds, opt_sds),
                  (p_sh_full, grads_sh, o_sh)))

    # gradient sync across DP axes (psum on replicated-across-data grads)
    if dp > 1 and not cfg.fsdp:
        from jax import shard_map
        axes = dist.batch_axes

        def sync_fn(grads):
            spec = jax.tree_util.tree_map(
                lambda l: P(*([None] * l.ndim)), grads)
            return shard_map(
                lambda g: jax.tree_util.tree_map(
                    lambda x: jax.lax.psum(x, axes), g),
                mesh=mesh, in_specs=(spec,), out_specs=spec,
                check_vma=False)(grads)
        comps.append(("grad_sync", 1, sync_fn, (params_sds,), (p_sh_full,)))
    return comps


def _forward_components(cfg, shape, dist, mesh,
                        unroll: bool = True) -> List[Tuple]:
    """Prefill: forward-only blocks + last-token head."""
    out = []
    acfg = _acfg(cfg, shape, unroll)
    b, s, d = shape.global_batch, shape.seq_len, cfg.d_model
    dt = cfg.compute_dtype
    api = get_model(cfg)
    params_sds = jax.eval_shape(
        functools.partial(api.init_params, cfg=cfg), jax.random.PRNGKey(0))
    h_sds = _sds((b, s, d), dt)
    h_sh = _h_sh(dist, b, s)
    pos_sds = _sds((b, s), jnp.int32)

    def fwd_only(name, mult, block_fn, lp_key):
        lp_sds = _slice_layer(params_sds[lp_key])
        lp_sh = param_shardings(lp_sds, dist)

        def f(lp, h, positions):
            return block_fn(lp, h, positions)
        out.append((name.replace("_grad", "_fwd"), mult, f,
                    (lp_sds, h_sds, pos_sds),
                    (lp_sh, h_sh, _batch_sh(dist, 2, b))))

    if cfg.family in ("dense", "moe", "mla_moe", "vlm"):
        def block_fn(lp, h, positions):
            o, aux = TF._block(lp, h, positions, acfg, dist, False)
            return o
        fwd_only("layer_grad", cfg.n_layers, block_fn, "layers")
    elif cfg.family == "ssm":
        def block_fn(lp, h, positions):
            hn = L.rmsnorm(h, lp["ln"], cfg.norm_eps)
            return h + SSM.mamba_block(lp["mamba"], hn, acfg)
        fwd_only("layer_grad", cfg.n_layers, block_fn, "layers")
    elif cfg.family == "hybrid":
        ng, _ = HY._n_groups(cfg)
        one_m = jax.tree_util.tree_map(
            lambda l: _sds(l.shape[2:], l.dtype), params_sds["groups"])
        out.append(("mamba_fwd", cfg.n_layers,
                    lambda lp, h, positions: h + SSM.mamba_block(lp, h, acfg),
                    (one_m, h_sds, pos_sds),
                    (param_shardings(one_m, dist), h_sh,
                     _batch_sh(dist, 2, b))))
        out.append(("shared_attn_fwd", ng,
                    lambda sp, h, positions: HY._shared_block(
                        sp, h, positions, acfg, False),
                    (params_sds["shared"], h_sds, pos_sds),
                    (param_shardings(params_sds["shared"], dist), h_sh,
                     _batch_sh(dist, 2, b))))
    elif cfg.family == "encdec":
        f_sds = _sds((b, cfg.n_frames, d), dt)
        enc_l = _slice_layer(params_sds["enc_layers"])
        dec_l = _slice_layer(params_sds["dec_layers"])
        epos = _sds((b, cfg.n_frames), jnp.int32)

        def enc_fn(lp, h, positions):
            a = L.attention_block(lp["attn"],
                                  L.rmsnorm(h, lp["ln1"], cfg.norm_eps),
                                  positions, acfg, causal=False)
            h = h + a
            return h + L.mlp_block(lp["mlp"],
                                   L.rmsnorm(h, lp["ln2"], cfg.norm_eps),
                                   cfg.mlp_type)
        out.append(("enc_layer_fwd", cfg.enc_layers, enc_fn,
                    (enc_l, f_sds, epos),
                    (param_shardings(enc_l, dist), _batch_sh(dist, 3, b),
                     _batch_sh(dist, 2, b))))

        def dec_fn(lp, h, enc_out, positions):
            a = L.attention_block(lp["self_attn"],
                                  L.rmsnorm(h, lp["ln1"], cfg.norm_eps),
                                  positions, acfg)
            h = h + a
            ek, ev = ED._cross_kv(lp["cross"], enc_out, acfg, False)
            c = ED._cross_attend(lp["cross"],
                                 L.rmsnorm(h, lp["ln2"], cfg.norm_eps),
                                 ek, ev, acfg, False)
            h = h + c
            return h + L.mlp_block(lp["mlp"],
                                   L.rmsnorm(h, lp["ln3"], cfg.norm_eps),
                                   cfg.mlp_type)
        out.append(("dec_layer_fwd", cfg.n_layers, dec_fn,
                    (dec_l, h_sds, f_sds, pos_sds),
                    (param_shardings(dec_l, dist), h_sh,
                     _batch_sh(dist, 3, b), _batch_sh(dist, 2, b))))

    # last-token head (embed fwd + unembed of one position)
    tok_sds = _sds((b, s), jnp.int32)
    head_keys = [k for k in ("embed", "final_norm", "lm_head") if
                 k in params_sds]
    hp_sds = {k: params_sds[k] for k in head_keys}

    def head_fn(hp, h_res, tokens):
        h = jnp.take(hp["embed"], tokens, axis=0).astype(dt) + h_res
        return TF.unembed(hp, h[:, -1:, :], cfg)
    out.append(("head_fwd", 1, head_fn, (hp_sds, h_sds, tok_sds),
                (param_shardings(hp_sds, dist), h_sh,
                 _batch_sh(dist, 2, b))))
    return out


def _decode_components(cfg, shape, dist, mesh,
                       gqsa: Optional[GQSAConfig]) -> List[Tuple]:
    api = get_model(cfg)
    b, s, d = shape.global_batch, shape.seq_len, cfg.d_model
    dt = cfg.compute_dtype
    params_sds = jax.eval_shape(
        functools.partial(api.init_params, cfg=cfg), jax.random.PRNGKey(0))
    if gqsa is not None:
        params_sds = compress_params_shapes(params_sds, cfg, gqsa)
    cache_sds = jax.eval_shape(
        functools.partial(api.init_cache, cfg, b, s))
    from repro.launch.steps import cache_shardings
    cache_sh_full = cache_shardings(cache_sds, b, s, dist)
    h_sds = _sds((b, 1, d), dt)
    h_sh = _batch_sh(dist, 3, b)
    pos_sds = _sds((), jnp.int32)
    pos_sh = _rep(dist, 0)
    comps = []

    def slice_cache(tree, sh_tree):
        return (jax.tree_util.tree_map(
            lambda l: _sds(l.shape[1:], l.dtype), tree),
            jax.tree_util.tree_map(
                lambda ns: NamedSharding(ns.mesh, P(*ns.spec[1:])), sh_tree))

    if cfg.family in ("dense", "moe", "mla_moe", "vlm"):
        lp_sds = _slice_layer(params_sds["layers"])
        lp_sh = param_shardings(lp_sds, dist)
        lc_sds, lc_sh = slice_cache(cache_sds, cache_sh_full)

        def block_dec(lp, lc, h, pos):
            hn = L.rmsnorm(h, lp["ln1"], cfg.norm_eps)
            if cfg.family == "mla_moe":
                a, new_c = MLA.mla_decode(lp["attn"], hn, lc, pos, cfg)
            else:
                a, new_c = L.attention_decode(lp["attn"], hn, lc, pos, cfg)
            h = h + a
            hn = L.rmsnorm(h, lp["ln2"], cfg.norm_eps)
            if cfg.moe is not None:
                m, _ = MOE.moe_block(lp["moe"], hn, cfg, dist)
            else:
                m = L.mlp_block(lp["mlp"], hn, cfg.mlp_type)
            return h + m, new_c
        comps.append(("layer_decode", cfg.n_layers, block_dec,
                      (lp_sds, lc_sds, h_sds, pos_sds),
                      (lp_sh, lc_sh, h_sh, pos_sh)))
    elif cfg.family == "ssm":
        lp_sds = _slice_layer(params_sds["layers"])
        lp_sh = param_shardings(lp_sds, dist)
        lc_sds, lc_sh = slice_cache(cache_sds, cache_sh_full)

        def block_dec(lp, lc, h, pos):
            hn = L.rmsnorm(h, lp["ln"], cfg.norm_eps)
            y, new_c = SSM.mamba_decode(lp["mamba"], hn, lc, cfg)
            return h + y, new_c
        comps.append(("layer_decode", cfg.n_layers, block_dec,
                      (lp_sds, lc_sds, h_sds, pos_sds),
                      (lp_sh, lc_sh, h_sh, pos_sh)))
    elif cfg.family == "hybrid":
        ng, _ = HY._n_groups(cfg)
        one_m = jax.tree_util.tree_map(
            lambda l: _sds(l.shape[2:], l.dtype), params_sds["groups"])
        mc_sds = jax.tree_util.tree_map(
            lambda l: _sds(l.shape[2:], l.dtype), cache_sds["groups"])
        mc_sh = jax.tree_util.tree_map(
            lambda ns: NamedSharding(ns.mesh, P(*ns.spec[2:])),
            cache_shardings(cache_sds, b, s, dist)["groups"])

        def mamba_dec(lp, lc, h, pos):
            y, new_c = SSM.mamba_decode(lp, h, lc, cfg)
            return h + y, new_c
        comps.append(("mamba_decode", cfg.n_layers, mamba_dec,
                      (one_m, mc_sds, h_sds, pos_sds),
                      (param_shardings(one_m, dist), mc_sh, h_sh, pos_sh)))

        kv_sds, kv_sh = slice_cache(cache_sds["attn"],
                                    cache_shardings(cache_sds, b, s,
                                                    dist)["attn"])
        sp_sds = params_sds["shared"]

        def attn_dec(sp, kv, h, pos):
            hn = L.rmsnorm(h, sp["ln1"], cfg.norm_eps)
            a, new_kv = HY._attn_decode_dist(sp, hn, kv, pos, cfg, dist,
                                             False)
            h = h + a
            m = L.mlp_block(sp["mlp"], L.rmsnorm(h, sp["ln2"], cfg.norm_eps),
                            cfg.mlp_type)
            return h + m, new_kv
        comps.append(("shared_attn_decode", ng, attn_dec,
                      (sp_sds, kv_sds, h_sds, pos_sds),
                      (param_shardings(sp_sds, dist), kv_sh, h_sh, pos_sh)))
    elif cfg.family == "encdec":
        dec_l = _slice_layer(params_sds["dec_layers"])
        lc_sds, lc_sh = slice_cache(cache_sds, cache_sh_full)

        def block_dec(lp, lc, h, pos):
            hn = L.rmsnorm(h, lp["ln1"], cfg.norm_eps)
            a, new_kv = L.attention_decode(lp["self_attn"], hn,
                                           {"k": lc["k"], "v": lc["v"]},
                                           pos, cfg)
            h = h + a
            hn = L.rmsnorm(h, lp["ln2"], cfg.norm_eps)
            q = jnp.reshape(
                jnp.einsum("bsd,od->bso", hn,
                           lp["cross"]["wq"]["w"].astype(hn.dtype))
                if "w" in lp["cross"]["wq"] else
                jnp.zeros((b, 1, cfg.n_heads * cfg.hd), hn.dtype),
                (b, 1, cfg.n_heads, cfg.hd))
            o = L.decode_attention(q, lc["cross_k"], lc["cross_v"],
                                   jnp.int32(cfg.n_frames))
            from repro.core.gqs_layer import apply_linear
            c = apply_linear(lp["cross"]["wo"], o.reshape(b, 1, -1))
            h = h + c
            m = L.mlp_block(lp["mlp"], L.rmsnorm(h, lp["ln3"], cfg.norm_eps),
                            cfg.mlp_type)
            return h + m, new_kv
        comps.append(("dec_layer_decode", cfg.n_layers, block_dec,
                      (dec_l, lc_sds, h_sds, pos_sds),
                      (param_shardings(dec_l, dist), lc_sh, h_sh, pos_sh)))

    # decode head: embed 1 token + unembed 1 position
    tok_sds = _sds((b, 1), jnp.int32)
    head_keys = [k for k in ("embed", "final_norm", "lm_head") if
                 k in params_sds]
    hp_sds = {k: params_sds[k] for k in head_keys}

    def head_dec(hp, h_res, tokens):
        h = jnp.take(hp["embed"], tokens, axis=0).astype(dt) + h_res
        return TF.unembed(hp, h, cfg)
    comps.append(("head_decode", 1, head_dec, (hp_sds, h_sds, tok_sds),
                  (param_shardings(hp_sds, dist), h_sh,
                   _batch_sh(dist, 2, b))))
    return comps


# ---------------------------------------------------------------------------

def analyze_cell(cfg: ModelConfig, shape: ShapeCfg, mesh, multi_pod: bool,
                 gqsa: Optional[GQSAConfig] = None,
                 accum: int = 1, sp_attention: bool = False) -> Dict:
    dist = make_dist(cfg, mesh, multi_pod, shape,
                     sp_attention=sp_attention)

    def build(unroll: bool):
        if shape.kind == "train":
            return _train_components(cfg, shape, dist, mesh, accum, unroll)
        if shape.kind == "prefill":
            return _forward_components(cfg, shape, dist, mesh, unroll)
        return _decode_components(cfg, shape, dist, mesh, gqsa)

    comps_u = build(True)    # pass A: exact flops + collectives
    comps_s = build(False)   # pass B: fused-kernel-like bytes

    per = {}
    tot = {"flops": 0.0, "bytes": 0.0, "coll": 0.0}
    for (cu, cs) in zip(comps_u, comps_s):
        name, mult, fn_u, sds_u, sh_u = cu[:5]
        out_sh = cu[5] if len(cu) > 5 else None
        fn_s, sds_s, sh_s = cs[2], cs[3], cs[4]
        rec = {"multiplier": mult}
        try:
            a = _compile_component(fn_u, sds_u, sh_u, mesh, out_sh)
            rec.update(flops=a["flops"], coll=a["coll"],
                       bytes_unrolled=a["bytes"])
        except Exception as e:
            per[name] = {"error": f"{type(e).__name__}: {e}"}
            continue
        if shape.kind == "decode":
            rec["bytes"] = a["bytes"]   # no inner scans in decode
        else:
            try:
                b = _compile_component(fn_s, sds_s, sh_s, mesh, out_sh)
                rec["bytes"] = b["bytes"]
            except Exception as e:
                rec["bytes"] = a["bytes"]
                rec["bytes_pass_error"] = f"{type(e).__name__}: {e}"
        per[name] = rec
        tot["flops"] += rec["flops"] * mult
        tot["bytes"] += rec["bytes"] * mult
        tot["coll"] += rec["coll"] * mult
    chips = mesh.devices.size
    mf = H.model_flops_estimate(cfg, shape)
    roof = H.roofline_terms({"flops": tot["flops"],
                             "bytes accessed": tot["bytes"]},
                            {"total": tot["coll"]}, chips, model_flops=mf)
    return {"components": per, "totals": tot,
            "roofline": roof.as_dict()}
