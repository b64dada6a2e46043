"""Step builders: train_step / prefill_step / serve_step with shardings.

These are the jit roots used by both the real launchers (train.py, serve.py)
and the multi-pod dry-run (dryrun.py lowers them against ShapeDtypeStructs).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeCfg
from repro.core.gqs_layer import GQSAConfig
from repro.core.model_compress import compress_params_shapes
from repro.dist.sharding import DistContext, param_shardings
from repro.models.registry import get_model, lm_loss
from repro.optim import adamw
from repro.optim.schedule import warmup_cosine


def make_dist(cfg: ModelConfig, mesh=None, multi_pod: bool = False,
              shape: Optional[ShapeCfg] = None,
              sp_attention: bool = False) -> DistContext:
    batch_axes = ("pod", "data") if multi_pod else ("data",)
    seq_axis = None
    if (shape is not None and shape.kind == "decode" and mesh is not None):
        dp = int(np.prod([mesh.shape[a] for a in batch_axes]))
        if shape.global_batch < dp and cfg.family in ("hybrid",):
            seq_axis = "data"   # sequence-sharded KV (distributed decode)
    return DistContext(mesh=mesh, batch_axes=batch_axes,
                       fsdp=cfg.fsdp, seq_axis=seq_axis,
                       sp_attention=sp_attention)


def batch_shardings(batch_tmpl: Dict, dist: DistContext):
    if dist.mesh is None:
        return None

    def one(leaf):
        b = leaf.shape[0]
        dp = int(np.prod([dist.axis_size(a) for a in dist.batch_axes]))
        spec = [None] * len(leaf.shape)
        if b % dp == 0 and b >= dp:
            spec[0] = dist.batch_axes
        return NamedSharding(dist.mesh, P(*spec))
    return jax.tree_util.tree_map(one, batch_tmpl)


def cache_shardings(cache_tmpl, batch: int, seq: int, dist: DistContext):
    """Cache leaves are [L(, G), B, S, inner...].

    * batch dim -> DP axes (when divisible);
    * ALSO one inner dim (KV heads / head_dim / MLA latent rank) -> model
      axis — without this the KV cache is the decode memory hog (e.g.
      yi-34b decode_32k: 1TB global / 16 DP shards = 64GB/dev; sharding
      head_dim over the 16-way model axis brings it to 4GB/dev);
    * batch too small to shard (long-context) -> sequence dim on 'data'.
    """
    if dist.mesh is None:
        return None
    dp = int(np.prod([dist.axis_size(a) for a in dist.batch_axes]))
    mp = dist.axis_size(dist.model_axis)

    def one(leaf):
        shape = leaf.shape
        spec = [None] * len(shape)
        b_idx = None
        if batch % dp == 0 and batch >= dp:
            for i, s in enumerate(shape):
                if s == batch:
                    spec[i] = dist.batch_axes
                    b_idx = i
                    break
        elif dist.seq_axis is not None:
            for i, s in enumerate(shape):
                if s == seq:
                    spec[i] = dist.seq_axis
                    b_idx = i
                    break
        if b_idx is not None:
            # inner dims live after the sequence dim: prefer the last dims
            # (KV-heads / head_dim / latent rank), skipping the seq dim
            for i in range(len(shape) - 1, b_idx + 1, -1):
                if shape[i] != seq and shape[i] % mp == 0 and shape[i] >= mp:
                    spec[i] = dist.model_axis
                    break
        return NamedSharding(dist.mesh, P(*spec))
    return jax.tree_util.tree_map(one, cache_tmpl)


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

def build_train_step(cfg: ModelConfig, dist: DistContext,
                     opt_cfg: adamw.AdamWConfig,
                     lr_fn=None, aux_weight: float = 1e-2,
                     accum_steps: int = 1, use_pallas: bool = False):
    api = get_model(cfg)
    lr_fn = lr_fn or (lambda step: opt_cfg.lr)

    def loss_fn(params, batch):
        logits, aux = api.forward(params, batch, cfg, dist, use_pallas)
        return lm_loss(logits, batch["labels"]) + aux_weight * aux

    def train_step(params, opt_state, batch):
        if accum_steps == 1:
            loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        else:
            def micro(carry, mb):
                acc_loss, acc_g = carry
                l, g = jax.value_and_grad(loss_fn)(params, mb)
                return (acc_loss + l,
                        jax.tree_util.tree_map(jnp.add, acc_g, g)), None
            micro_batch = jax.tree_util.tree_map(
                lambda x: x.reshape((accum_steps, -1) + x.shape[1:]), batch)
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (loss, grads), _ = jax.lax.scan(
                micro, (jnp.float32(0.0), zeros), micro_batch)
            loss = loss / accum_steps
            grads = jax.tree_util.tree_map(lambda g: g / accum_steps, grads)
        lr = lr_fn(opt_state["step"])
        params, opt_state, gnorm = adamw.apply_updates(
            params, grads, opt_state, opt_cfg, lr)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return params, opt_state, metrics

    return train_step


def train_templates(cfg: ModelConfig, shape: ShapeCfg, dist: DistContext):
    """(params_sds, opt_sds, batch_sds, in_shardings) — no allocation."""
    from repro.configs.registry import input_specs
    api = get_model(cfg)
    rng = jax.random.PRNGKey(0)
    params_sds = jax.eval_shape(functools.partial(api.init_params, cfg=cfg),
                                rng)
    opt_sds = jax.eval_shape(adamw.init_state, params_sds)
    batch_sds = input_specs(cfg, shape)
    p_sh = param_shardings(params_sds, dist)
    o_sh = {"m": p_sh, "v": p_sh,
            "step": NamedSharding(dist.mesh, P()) if dist.mesh else None}
    b_sh = batch_shardings(batch_sds, dist)
    return params_sds, opt_sds, batch_sds, (p_sh, o_sh, b_sh)


def build_train_step_ddp(cfg: ModelConfig, dist: DistContext,
                         opt_cfg: adamw.AdamWConfig, lr_fn=None,
                         aux_weight: float = 1e-2, compress: bool = True):
    """shard_map DDP train step with int8 error-feedback gradient all-reduce
    (params replicated; for models that fit per-device — the paper's own
    llama-2-7b class). State gains an ``err`` tree (error feedback)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    from repro.optim import grad_compress as GC
    api = get_model(cfg)
    lr_fn = lr_fn or (lambda step: opt_cfg.lr)
    axes = dist.batch_axes

    def local_step(params, opt_state, err, batch):
        def loss_fn(p):
            logits, aux = api.forward(p, batch, cfg, None)
            return lm_loss(logits, batch["labels"]) + aux_weight * aux
        loss, grads = jax.value_and_grad(loss_fn)(params)
        loss = jax.lax.pmean(loss, axes)
        if compress:
            grads, err = GC.allreduce_compressed(grads, err, axes)
        else:
            grads = GC.allreduce_mean(grads, axes)
        lr = lr_fn(opt_state["step"])
        params, opt_state, gnorm = adamw.apply_updates(
            params, grads, opt_state, opt_cfg, lr)
        return params, opt_state, err, {"loss": loss, "grad_norm": gnorm,
                                        "lr": lr}

    if dist.mesh is None:
        # single-device fallback: no collective, no compression effect
        def step1(params, opt_state, err, batch):
            def loss_fn(p):
                logits, aux = api.forward(p, batch, cfg, None)
                return lm_loss(logits, batch["labels"]) + aux_weight * aux
            loss, grads = jax.value_and_grad(loss_fn)(params)
            lr = lr_fn(opt_state["step"])
            params, opt_state, gnorm = adamw.apply_updates(
                params, grads, opt_state, opt_cfg, lr)
            return params, opt_state, err, {"loss": loss,
                                            "grad_norm": gnorm, "lr": lr}
        return step1

    rep = lambda t: jax.tree_util.tree_map(
        lambda l: P(*([None] * getattr(l, "ndim", 0))), t)

    def step(params, opt_state, err, batch):
        p_spec = rep(params)
        o_spec = rep(opt_state)
        e_spec = rep(err)
        b_spec = jax.tree_util.tree_map(
            lambda l: P(axes, *([None] * (l.ndim - 1))), batch)
        m_spec = {"loss": P(), "grad_norm": P(), "lr": P()}
        return shard_map(local_step, mesh=dist.mesh,
                         in_specs=(p_spec, o_spec, e_spec, b_spec),
                         out_specs=(p_spec, o_spec, e_spec, m_spec),
                         check_vma=False)(params, opt_state, err, batch)

    return step


# ---------------------------------------------------------------------------
# prefill / serve
# ---------------------------------------------------------------------------

def build_prefill_step(cfg: ModelConfig, dist: DistContext,
                       use_pallas: bool = False):
    api = get_model(cfg)

    def prefill_step(params, batch):
        logits, _ = api.forward(params, batch, cfg, dist, use_pallas,
                                last_only=True)
        return jnp.argmax(logits[:, -1, :], axis=-1)

    return prefill_step


def build_serve_step(cfg: ModelConfig, dist: DistContext,
                     use_pallas: bool = False):
    api = get_model(cfg)

    def serve_step(params, cache, tokens, pos):
        logits, new_cache = api.decode_step(params, cache, tokens, pos, cfg,
                                            dist, use_pallas)
        next_tok = jnp.argmax(logits[:, -1, :], axis=-1, keepdims=True)
        return next_tok.astype(jnp.int32), new_cache

    return serve_step


def serve_templates(cfg: ModelConfig, shape: ShapeCfg, dist: DistContext,
                    gqsa: Optional[GQSAConfig]):
    """(packed_params_sds, cache_sds, tokens_sds, in_shardings)."""
    api = get_model(cfg)
    rng = jax.random.PRNGKey(0)
    params_sds = jax.eval_shape(functools.partial(api.init_params, cfg=cfg),
                                rng)
    if gqsa is not None:
        params_sds = compress_params_shapes(params_sds, cfg, gqsa)
    b = shape.global_batch
    cache_sds = jax.eval_shape(
        functools.partial(api.init_cache, cfg, b, shape.seq_len))
    tokens_sds = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    pos_sds = jax.ShapeDtypeStruct((), jnp.int32)
    p_sh = param_shardings(params_sds, dist)
    c_sh = cache_shardings(cache_sds, b, shape.seq_len, dist)
    t_sh = batch_shardings({"t": tokens_sds}, dist)
    t_sh = t_sh["t"] if t_sh else None
    pos_sh = NamedSharding(dist.mesh, P()) if dist.mesh else None
    return (params_sds, cache_sds, tokens_sds, pos_sds,
            (p_sh, c_sh, t_sh, pos_sh))
