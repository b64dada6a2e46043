"""Custom collectives: int8 error-feedback gradient all-reduce and
sequence-sharded decode attention (distributed flash-decoding).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# int8 error-feedback psum (EF-SGD)
# ---------------------------------------------------------------------------

def compressed_psum_leaf(g: jnp.ndarray, err: jnp.ndarray, axis: str):
    """One leaf inside shard_map: returns (mean over axis, new error).

    (g + err) is quantized to int8 with a pmax-shared per-tensor scale,
    psum'd exactly in int32, dequantized; the local quantization residual
    becomes the next step's error feedback."""
    gf = g.astype(jnp.float32) + err
    amax = jax.lax.pmax(jnp.max(jnp.abs(gf)), axis)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    deq = q.astype(jnp.float32) * scale
    n = jax.lax.psum(jnp.ones((), jnp.float32), axis)
    mean = jax.lax.psum(q.astype(jnp.int32), axis).astype(jnp.float32) \
        * scale / n
    return mean, gf - deq


def compressed_psum(grads, err_state, axis: str):
    """Tree version: returns (mean grads, new error state)."""
    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_e = jax.tree_util.tree_leaves(err_state)
    out = [compressed_psum_leaf(g, e, axis)
           for g, e in zip(flat_g, flat_e)]
    return (jax.tree_util.tree_unflatten(treedef, [o[0] for o in out]),
            jax.tree_util.tree_unflatten(treedef, [o[1] for o in out]))


# ---------------------------------------------------------------------------
# sequence-sharded decode (long-context: KV cache sharded over 'data')
# ---------------------------------------------------------------------------

def update_sharded_cache(cache: jnp.ndarray, new: jnp.ndarray,
                         pos, mesh, axis: str) -> jnp.ndarray:
    """Write ``new`` [B, 1, KH, D] at sequence position ``pos`` of a cache
    [B, S, KH, D] sharded over ``axis`` on the S dim. Only the owning
    shard writes; others pass their slice through."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    s = cache.shape[1]
    s_loc = s // int(mesh.shape[axis])

    def local(c, nw, p):
        start = jax.lax.axis_index(axis) * s_loc
        off = jnp.clip(p - start, 0, s_loc - 1)
        upd = jax.lax.dynamic_update_slice(
            c, nw.astype(c.dtype), (0, off, 0, 0))
        mine = (p >= start) & (p < start + s_loc)
        return jnp.where(mine, upd, c)

    return shard_map(local, mesh=mesh,
                     in_specs=(P(None, axis), P(), P()),
                     out_specs=P(None, axis), check_vma=False)(
                         cache, new, jnp.asarray(pos, jnp.int32))


def sharded_decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                             v_cache: jnp.ndarray, length, mesh,
                             axis: str) -> jnp.ndarray:
    """Flash-decoding over a sequence-sharded KV cache: each shard computes
    partial (max, exp-sum, weighted values) over its local keys; pmax/psum
    combine to the exact softmax. q: [B, 1, H, D]; caches: [B, S, KH, D]."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    b, s, kh, d = k_cache.shape
    h = q.shape[2]
    r = h // kh
    nsh = int(mesh.shape[axis])
    s_loc = s // nsh
    scale = 1.0 / jnp.sqrt(jnp.float32(d))

    def local(qq, kl, vl, ln):
        start = jax.lax.axis_index(axis) * s_loc
        qh = qq.reshape(b, kh, r, d).astype(jnp.float32)
        sco = jnp.einsum("bkrd,bskd->bkrs", qh,
                         kl.astype(jnp.float32)) * scale
        pos = start + jnp.arange(s_loc)
        valid = pos[None, :] < jnp.reshape(ln, (-1, 1))
        sco = jnp.where(valid[:, None, None, :], sco, -jnp.inf)
        m = jax.lax.pmax(jnp.max(sco, axis=-1), axis)
        msafe = jnp.where(jnp.isinf(m), 0.0, m)
        p = jnp.where(jnp.isinf(sco), 0.0, jnp.exp(sco - msafe[..., None]))
        l = jax.lax.psum(jnp.sum(p, axis=-1), axis)
        o = jax.lax.psum(
            jnp.einsum("bkrs,bskd->bkrd", p, vl.astype(jnp.float32)), axis)
        o = o / jnp.maximum(l, 1e-30)[..., None]
        return o.reshape(b, 1, h, d).astype(qq.dtype)

    return shard_map(local, mesh=mesh,
                     in_specs=(P(), P(None, axis), P(None, axis), P()),
                     out_specs=P(), check_vma=False)(
                         q, k_cache, v_cache,
                         jnp.asarray(length, jnp.int32))
