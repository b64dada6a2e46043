"""Plain reference of a DeepSeek-V2 MLA + MoE decoder with GQSA-compressed
linears.

Written from the published architecture (DeepSeek-V2, arXiv:2405.04434,
and its modelling code): pre-norm RMSNorm blocks; multi-head latent
attention with a direct q projection, the latent KV row (RMS-normed
``c_kv`` and one shared rope key) up-projected per head to K and V
(unabsorbed), RoPE on the rope dims with YaRN frequencies and the softmax
scale times mscale(factor, mscale_all_dim)²; leading dense SwiGLU
layers; MoE layers whose softmax router picks the top-k experts greedily
with gates that are the top-k probabilities times the scaling factor
(no renormalisation), every routed expert computed densely over every
token and combined by the gates, plus the shared experts; an untied head.
RoPE rotates split halves (see the configuration's ``assumed``).

float32 at ``highest`` matmul precision. It imports nothing of the
program under test: every weight is drawn again from the configuration's
weight seed by ``bench/weights.py`` and ``bench/models/mla_moe.py`` and
dequantized here, layer by layer, inside the jitted layer function.

``mode="fp8"`` is the control: the same computation with every matmul
operand rounded to float8 e4m3 under a per-tensor or per-row amax scale,
the precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.mla_moe import (DENSE_IDS, MOE_IDS, Model,
                                  unpacked_leaves)
from bench.references.dense_transformer import (HI, ROW_BLOCK, _dequant,
                                                _fp8, _head_gaps, _mm,
                                                _rms, check_sample)
from bench.weights import Compression, canonical, slice_key

__all__ = ["served_gaps", "check_sample"]
Q_BLOCK = 256          # query rows per attention block


def yarn(model: Model):
    """(inverse frequencies [rope/2], softmax scale) of DeepSeek-V2's
    YaRN rotary embedding, as its modelling code computes them."""
    y = dict(model.yarn)
    dim, base = model.rope, model.rope_theta

    def corr(rot):
        return dim * math.log(y["original_max_position"]
                              / (rot * 2 * math.pi)) / (2 * math.log(base))

    def mscale(m):
        return 0.1 * m * math.log(y["factor"]) + 1.0 \
            if y["factor"] > 1 else 1.0
    low = max(math.floor(corr(y["beta_fast"])), 0)
    high = min(math.ceil(corr(y["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    i = np.arange(dim // 2, dtype=np.float64)
    ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
    extra = 1.0 / base ** (2 * i / dim)
    inv = extra / y["factor"] * ramp + extra * (1.0 - ramp)
    scale = (model.nope + model.rope) ** -0.5
    if y["mscale_all_dim"]:
        scale *= mscale(y["mscale_all_dim"]) ** 2
    # cos and sin carry mscale / mscale_all_dim, 1 where they are equal
    gain = mscale(y["mscale"]) / mscale(y["mscale_all_dim"])
    return inv.astype(np.float32), scale, gain


def _rope(x, inv, gain):
    """x [B, L, H, D] at positions 0..L-1, rotating split halves."""
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1) * gain


def _attention(q, k, v, scale, mode):
    """Causal MHA. q, k [B, L, H, Dq]; v [B, L, H, Dv] -> [B, L, H*Dv]."""
    b, l, h, _ = q.shape
    if mode == "fp8":
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    outs = []
    for s0 in range(0, l, Q_BLOCK):
        qb = q[:, s0:s0 + Q_BLOCK]
        sco = jnp.einsum("bqhd,bkhd->bhqk", qb, k, precision=HI) * scale
        qpos = s0 + jnp.arange(qb.shape[1])
        sco = jnp.where(qpos[:, None] >= jnp.arange(l)[None, :], sco,
                        -jnp.inf)
        p = jax.nn.softmax(sco, axis=-1)
        if mode == "fp8":
            p = _fp8(p, -1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI))
    return jnp.concatenate(outs, axis=1).reshape(b, l, -1)


def _swiglu(x, wg, wu, wd, mode):
    return _mm(jax.nn.silu(_mm(x, wg, mode)) * _mm(x, wu, mode), wd, mode)


@functools.partial(jax.jit, static_argnames=("model", "comp", "wseed",
                                              "mode", "stack"))
def _layer(h, layer, leaves, *, model: Model, comp: Compression,
           wseed: int, mode: str, stack: str):
    """One layer of ``stack`` ("dense_layers" or "layers") at its index
    ``layer``; ``leaves`` are that layer's unpacked leaves."""
    g = comp.group_size
    ids = DENSE_IDS if stack == "dense_layers" else MOE_IDS

    def w(name, n, k, index=()):
        return _dequant(*canonical(slice_key(wseed, (layer,) + index,
                                             ids[name]), n, k, g,
                                   comp.kept(k)), g)

    inv, scale, gain = yarn(model)
    b, l, _ = h.shape
    x = _rms(h, model.eps)
    dims = {name: (n, k) for name, n, k in model.attn_linears()}
    q = _mm(x, w("wq", *dims["wq"]), mode).reshape(
        b, l, model.heads, model.nope + model.rope)
    q_nope, q_rope = jnp.split(q, [model.nope], axis=-1)
    kv = _mm(x, w("w_kva", *dims["w_kva"]), mode)
    c_kv = _rms(kv[..., :model.kv_rank], model.eps) * leaves["kv_norm"]
    k_rope = _rope(kv[..., None, model.kv_rank:], inv, gain)
    k_nope = jnp.einsum("blr,hdr->blhd", c_kv, leaves["w_uk"],
                        precision=HI)
    v = jnp.einsum("blr,hdr->blhd", c_kv, leaves["w_uv"], precision=HI)
    qq = jnp.concatenate([q_nope, _rope(q_rope, inv, gain)], -1)
    kk = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, (b, l, model.heads, model.rope))], -1)
    h = h + _mm(_attention(qq, kk, v, scale, mode), w("wo", *dims["wo"]),
                mode)
    x = _rms(h, model.eps)
    if stack == "dense_layers":
        return h + _swiglu(x, *[w(n, *nk) for n, *nk in
                                model.swiglu(model.d_ff, model.d)], mode)
    shared = [w("shared." + n, *nk) for n, *nk in
              model.swiglu(model.shared * model.d_expert, model.d)]
    y = _swiglu(x, *shared, mode)
    probs = jax.nn.softmax(_mm(x, leaves["router"], mode), axis=-1)
    top, idx = jax.lax.top_k(probs, model.top_k)
    gates = top / jnp.sum(top, -1, keepdims=True) if model.norm_topk \
        else top * model.scaling
    # [B, L, E]: each expert's gate for each token, 0 where not chosen
    gate_of = jnp.sum(jax.nn.one_hot(idx, model.experts) * gates[..., None],
                      axis=-2)

    def expert(acc, e):
        ws = [w("experts." + n, *nk, index=(e,)) for n, *nk in
              model.swiglu(model.d_expert, model.d)]
        return acc + _swiglu(x, *ws, mode) * gate_of[..., e, None], None
    routed, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                             jnp.arange(model.experts))
    return h + y + routed


def _final_hidden(conf: Dict, batch: np.ndarray, mode: str):
    model = Model.from_conf(conf)
    comp = Compression.from_conf(conf["compression"])
    wseed = int(conf["weight_seed"])
    dt = jnp.dtype(conf["leaf_dtype"])
    dense = unpacked_leaves(model, wseed, dt)
    h = dense["embed"].astype(jnp.float32)[jnp.asarray(batch)]
    for stack, n in (("dense_layers", model.dense_layers),
                     ("layers", model.moe_layers)):
        for layer in range(n):
            leaves = {k: v[layer].astype(jnp.float32)
                      for k, v in dense[stack].items()
                      if k in ("kv_norm", "w_uk", "w_uv", "router")}
            h = _layer(h, jnp.int32(layer), leaves, model=model, comp=comp,
                       wseed=wseed, mode=mode, stack=stack)
    head = dense["embed"] if model.tied else dense["lm_head"]
    return _rms(h, model.eps), head.astype(jnp.float32)


def served_gaps(conf: Dict, prompts: Sequence[np.ndarray],
                served: Sequence[np.ndarray], length: int, rows_n: int,
                control: bool = False) -> Dict[str, np.ndarray]:
    """Run the reference once over each prompt followed by its served
    tokens (all padded to ``length``, in a batch of ``rows_n`` rows so
    that every run compiles the same shapes) and return, for every served
    token, how far its reference logit lies below the reference's best
    (``gap``). With ``control`` the fp8 computation runs too, and
    ``control_gap`` gives the same reading for the token it puts first
    at each of those positions."""
    if len(prompts) > rows_n:
        raise ValueError(f"{len(prompts)} sequences for {rows_n} rows")
    batch = np.zeros((rows_n, length), np.int32)
    rows, toks = [], []
    for i, (p, s) in enumerate(zip(prompts, served)):
        seq = np.concatenate([p, s]).astype(np.int32)
        if seq.shape[0] > length:
            raise ValueError(f"sequence of {seq.shape[0]} tokens exceeds "
                             f"the reference length {length}")
        batch[i, :seq.shape[0]] = seq
        # the logits at position t predict the token at t + 1
        rows += [i * length + len(p) - 1 + j for j in range(len(s))]
        toks += [int(t) for t in s]
    r = len(rows)
    pad = -(-r // ROW_BLOCK) * ROW_BLOCK - r
    rows_a = jnp.asarray(np.array(rows + [0] * pad, np.int32))
    toks_a = jnp.asarray(np.array(toks + [0] * pad, np.int32))
    hid, head = _final_hidden(conf, batch, "ref")
    ref_rows = hid.reshape(-1, hid.shape[-1])[rows_a]
    if control:
        chid, _ = _final_hidden(conf, batch, "fp8")
        ctrl_rows = chid.reshape(-1, chid.shape[-1])[rows_a]
        gap, cgap = _head_gaps(ctrl_rows, ref_rows, head, toks_a,
                               mode="fp8")
    else:
        gap, cgap = _head_gaps(ref_rows, ref_rows, head, toks_a,
                               mode="ref")
    out = {"gap": np.asarray(gap)[:r]}
    if control:
        out["control_gap"] = np.asarray(cgap)[:r]
    return out
