"""Plain reference of a dense GQA decoder with GQSA-compressed linears.

Written from the published architecture (pre-norm RMSNorm blocks, RoPE
on split halves, grouped-query causal attention, a tanh-GELU or SwiGLU
MLP, tied or untied output head) in float32 at ``highest`` matmul
precision. It imports nothing of the program under test: every weight is
drawn again from the configuration's weight seed by ``bench/weights.py``
and ``bench/models/dense_transformer.py`` and dequantized here, layer by
layer, inside the jitted layer function, so no weight tree is ever held
whole.

``mode="fp8"`` is the control: the same computation with every matmul
operand (weights, activations, attention scores and probabilities)
rounded to float8 e4m3 under a per-tensor or per-row amax scale, the
precision step below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.dense_transformer import Model, dense_leaves, linear_key
from bench.weights import Compression, canonical

HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256          # query rows per attention block
ROW_BLOCK = 512        # positions per block of head logits
FP8_MAX = 448.0


def _fp8(x, axis):
    """Round to float8 e4m3 under an amax scale over ``axis`` (None: the
    whole tensor), returned in f32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.maximum(amax, 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(x, w, mode):
    """x [..., K] @ w[N, K].T in f32."""
    if mode == "fp8":
        x, w = _fp8(x, -1), _fp8(w, None)
    return jnp.einsum("...k,nk->...n", x, w, precision=HI)


def _dequant(codes, gmask, scale, zero, g):
    n, k = codes.shape
    c = codes.reshape(n, k // g, g).astype(jnp.float32)
    w = (c - zero[..., None]) * scale[..., None] * gmask[..., None]
    return w.reshape(n, k)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """x [B, L, H, D] at positions 0..L-1, rotating split halves."""
    d = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, mode):
    """Causal GQA. q [B, L, H, D]; k, v [B, L, KH, D] -> [B, L, H*D]."""
    b, l, h, d = q.shape
    kh = k.shape[2]
    k = jnp.repeat(k, h // kh, axis=2)
    v = jnp.repeat(v, h // kh, axis=2)
    if mode == "fp8":
        q, k, v = _fp8(q, -1), _fp8(k, -1), _fp8(v, -1)
    outs = []
    for s0 in range(0, l, Q_BLOCK):
        qb = q[:, s0:s0 + Q_BLOCK]
        sco = jnp.einsum("bqhd,bkhd->bhqk", qb, k,
                         precision=HI) / np.sqrt(d)
        qpos = s0 + jnp.arange(qb.shape[1])
        sco = jnp.where(qpos[:, None] >= jnp.arange(l)[None, :], sco,
                        -jnp.inf)
        p = jax.nn.softmax(sco, axis=-1)
        if mode == "fp8":
            p = _fp8(p, -1)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI))
    return jnp.concatenate(outs, axis=1).reshape(b, l, h * d)


@functools.partial(jax.jit, static_argnames=("model", "comp", "wseed",
                                              "mode"))
def _layer(h, layer, *, model: Model, comp: Compression, wseed: int,
           mode: str):
    g = comp.group_size

    def w(name, n, k):
        return _dequant(*canonical(linear_key(wseed, layer, name), n, k, g,
                                   comp.kept(k)), g)

    dims = {name: (n, k) for _, name, n, k in model.linears()}
    b, l, _ = h.shape
    x = _rms(h, model.eps)
    q = _mm(x, w("wq", *dims["wq"]), mode).reshape(
        b, l, model.heads, model.head_dim)
    k = _mm(x, w("wk", *dims["wk"]), mode).reshape(
        b, l, model.kv_heads, model.head_dim)
    v = _mm(x, w("wv", *dims["wv"]), mode).reshape(
        b, l, model.kv_heads, model.head_dim)
    q, k = _rope(q, model.rope_theta), _rope(k, model.rope_theta)
    h = h + _mm(_attention(q, k, v, mode), w("wo", *dims["wo"]), mode)
    x = _rms(h, model.eps)
    u = _mm(x, w("wu", *dims["wu"]), mode)
    if model.gated:
        u = jax.nn.silu(_mm(x, w("wg", *dims["wg"]), mode)) * u
    else:
        u = jax.nn.gelu(u, approximate=True)
    return h + _mm(u, w("wd", *dims["wd"]), mode)


@functools.partial(jax.jit, static_argnames=("mode",))
def _head_gaps(hid, ref_hid, head, tokens, *, mode: str):
    """For rows of final hidden states: the reference's best logit minus
    its logit of ``tokens`` (the served tokens) and of the token the
    ``mode`` computation puts first. hid/ref_hid [R, d], head [V, d]."""
    def block(args):
        x, xr, tok = args
        ref = _mm(xr, head, "ref")
        best = ref.max(-1)
        served = jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]
        top = jnp.argmax(_mm(x, head, mode), -1)
        picked = jnp.take_along_axis(ref, top[:, None], -1)[:, 0]
        return best - served, best - picked
    r, d = hid.shape
    args = (hid.reshape(-1, ROW_BLOCK, d), ref_hid.reshape(-1, ROW_BLOCK, d),
            tokens.reshape(-1, ROW_BLOCK))
    served, picked = jax.lax.map(block, args)
    return served.reshape(r), picked.reshape(r)


def _final_hidden(conf: Dict, batch: np.ndarray, mode: str):
    model = Model.from_conf(conf)
    comp = Compression.from_conf(conf["compression"])
    wseed = int(conf["weight_seed"])
    dense = dense_leaves(model, wseed, jnp.dtype(conf["leaf_dtype"]))
    h = dense["embed"].astype(jnp.float32)[jnp.asarray(batch)]
    for layer in range(model.layers):
        h = _layer(h, jnp.int32(layer), model=model, comp=comp,
                   wseed=wseed, mode=mode)
    head = dense["embed"] if model.tied else dense["lm_head"]
    return _rms(h, model.eps), head.astype(jnp.float32)


def served_gaps(conf: Dict, prompts: Sequence[np.ndarray],
                served: Sequence[np.ndarray], length: int, rows_n: int,
                control: bool = False) -> Dict[str, np.ndarray]:
    """Run the reference once over each prompt followed by its served
    tokens (all padded to ``length``, in a batch of ``rows_n`` rows so
    that every run compiles the same shapes) and return, for every served
    token,
    how far its reference logit lies below the reference's best
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


def check_sample(lengths: List[int], rng: np.random.Generator,
                 size: int) -> List[int]:
    """Indices of the requests to compare: the one with the most served
    tokens, then others drawn from ``rng``."""
    order = list(np.argsort(lengths, kind="stable")[::-1])
    first, rest = order[:1], order[1:]
    picked = list(rng.choice(rest, size=min(size - 1, len(rest)),
                             replace=False)) if rest else []
    return [int(i) for i in first + picked]
