"""GQS layer (paper §3.2): the drop-in replacement for Linear.

A linear layer's parameters take one of four *representations*; the model
code calls :func:`apply_linear` and dispatches on which leaves are present,
so the same model definition runs FP training, fake-quant optimization
(BQPO / E2E-OQP), and packed-BSR serving.

    fp          {"w": [N,K] (, "b")}
    fake_quant  {"w", "gmask" [N,K/G] bool (, "scale","zero" [N,K/G])}
    w4          {"qw" int32 [Kp/8,N], "scale","zero" [8,Kp/8G,N]}  dense quant
    gqsa        {"bsr": BSRMatrix}                                  quant+sparse

The packed forms are laid out for their TPU kernels at pack time
(kernels/w4_matmul.py, core/bsr.py). ``use_pallas`` picks the kernels
(`kernels/ops.py`) or their plain-jnp oracles (`kernels/ref.py`).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.core import pruning
from repro.core.bsr import BSRMatrix, pack_dense
from repro.core.quant import (QuantConfig, fake_quant, group_minmax_params,
                              quantize)
from repro.core.pruning import PruneConfig, expand_mask, group_mask
from repro.core.saliency import (HessianStats, group_saliency,
                                 weight_saliency)
from repro.kernels import ops as kops
from repro.kernels import ref as kref


@dataclasses.dataclass(frozen=True)
class GQSAConfig:
    """End-to-end compression configuration (paper W4 S{20..50} G16).

    ``saliency``: "hessian" (paper eq. 4, diag approx), "wanda"
    (|w|*sqrt(E x^2)) or "magnitude" (w^2). On our from-scratch benchmark
    models the shared per-input-dim Hessian factor correlates row masks
    (prunes whole input dims) and magnitude wins one-shot; with the full
    two-stage pipeline all three converge (see benchmarks/fig_saliency).
    """
    quant: QuantConfig = QuantConfig(bits=4, group_size=16)
    prune: PruneConfig = PruneConfig(sparsity=0.5, group_size=16,
                                     row_balanced=True)
    exact_hessian: bool = False
    saliency: str = "hessian"

    def __post_init__(self):
        if self.quant.group_size != self.prune.group_size:
            raise ValueError("quant and prune group sizes must match: the "
                             "group is both the quant and the prune unit")


def apply_linear(p: Dict, x: jnp.ndarray, *, qcfg: Optional[QuantConfig] = None,
                 use_pallas: bool = False, label: str = "") -> jnp.ndarray:
    """x: [..., K] -> [..., N]; dispatch on the parameter representation.
    ``label`` (static: ``wq`` ... ``wd``) names the GQSA kernels of this
    linear in a device trace (``gqsa_gemv_<label>``,
    ``gqsa_densify_<label>``)."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    x2 = x.reshape(-1, k)
    if isinstance(p, dict) and "bsr" in p:
        if use_pallas:
            y = kops.gqsa_gemv(x2, p["bsr"], label)
        else:
            y = kref.gqsa_gemv_ref(x2, p["bsr"])
        y = y.astype(x.dtype)
    elif isinstance(p, dict) and "qw" in p:
        mm = kops.w4_matmul if use_pallas else kref.w4_matmul_ref
        y = mm(x2, p["qw"], p["scale"], p["zero"]).astype(x.dtype)
    elif isinstance(p, dict) and "q" in p:
        # E2E-OQP: frozen INT codes, trainable (scale, zero) — dequant is
        # linear in (s, z) so gradients flow to them with no STE
        from repro.core.quant import dequantize
        k2 = p["q"].shape[-1]
        g = k2 // p["scale"].shape[-1]
        w = dequantize(jax.lax.stop_gradient(p["q"]), p["scale"], p["zero"],
                       QuantConfig(group_size=g))
        mask = expand_mask(jax.lax.stop_gradient(p["gmask"]),
                           g).astype(w.dtype)
        y = x2 @ (w * mask).astype(x.dtype).T
    elif isinstance(p, dict) and "gmask" in p:
        if qcfg is None:
            # group structure is encoded in the mask; bits default to the
            # paper's W4
            g = p["w"].shape[-1] // p["gmask"].shape[-1]
            qcfg = QuantConfig(bits=4, group_size=g)
        w = fake_quant(p["w"], qcfg, p.get("scale"), p.get("zero"))
        mask = expand_mask(jax.lax.stop_gradient(p["gmask"]),
                           qcfg.group_size).astype(w.dtype)
        y = x2 @ (w * mask).astype(x.dtype).T
    else:
        # params may be stored f32; compute in the activation dtype
        y = x2 @ p["w"].astype(x.dtype).T
    if isinstance(p, dict) and "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y.reshape(*lead, -1)


# ---------------------------------------------------------------------------
# Representation conversions (the offline compression steps).
# ---------------------------------------------------------------------------

def make_fake_quant(w: jnp.ndarray, stats: HessianStats,
                    cfg: GQSAConfig, with_qparams: bool = False) -> Dict:
    """FP weight + calibration stats -> fake-quant params (stage-1 input)."""
    sal = weight_saliency(w, stats, exact=cfg.exact_hessian)
    gsal = group_saliency(sal, cfg.prune.group_size)
    gmask = group_mask(gsal, cfg.prune)
    p = {"w": w, "gmask": gmask}
    if with_qparams:
        s, z = group_minmax_params(w, cfg.quant)
        p["scale"], p["zero"] = s, z
    return p


def pack_gqsa(p_fake: Dict, cfg: GQSAConfig) -> Dict:
    """fake-quant params -> packed BSR serving params."""
    return {"bsr": pack_dense(p_fake["w"], p_fake["gmask"], cfg.quant)}


def pack_w4(w: jnp.ndarray, qcfg: QuantConfig) -> Dict:
    """FP weight -> dense W<=4 serving params (quantization-only baseline),
    in the plane-packed layout of ``kernels/w4_matmul.py``: K padded to a
    multiple of 8·G with zero codes and scales, word r of the [Kp/8, N]
    int32 ``qw`` holding the codes of input columns r + i·Kp/8 in nibble
    i. Nibble packing only holds codes < 16; wider bit-widths use the
    fake-quant (dense FP) representation instead."""
    if qcfg.bits > 4:
        raise ValueError("pack_w4 packs 4-bit nibbles: bits must be "
                         "<= 4 (use fake_quant for W8)")
    n, k = w.shape
    g = qcfg.group_size
    s, z = group_minmax_params(w, qcfg)
    q = quantize(w, s, z, qcfg)
    kp = -(-k // (8 * g)) * (8 * g)
    pad = ((0, kp - k), (0, 0))
    planes = jnp.pad(q.T.astype(jnp.uint32), pad).reshape(8, kp // 8, n)
    words = jnp.zeros((kp // 8, n), jnp.uint32)
    for i in range(8):
        words = words | (planes[i] << (4 * i))

    def groups(a):                                # [N, K/G] -> [8, Kp/8G, N]
        return jnp.pad(a.T, ((0, (kp - k) // g), (0, 0))).reshape(
            8, kp // (8 * g), n).astype(jnp.float32)
    return {"qw": jax.lax.bitcast_convert_type(words, jnp.int32),
            "scale": groups(s), "zero": groups(z)}


def compress_linear(w: jnp.ndarray, stats: HessianStats,
                    cfg: GQSAConfig) -> Dict:
    """One-shot (no BQPO) FP -> packed GQSA params."""
    return pack_gqsa(make_fake_quant(w, stats, cfg), cfg)


# ---------------------------------------------------------------------------
# Shape-only construction for the dry-run (no allocation, no numpy loops).
# ---------------------------------------------------------------------------

def packed_linear_shapes(n: int, k: int, cfg: GQSAConfig) -> Dict:
    """ShapeDtypeStructs of the packed representation for (n, k), as
    ``core/bsr.py:pack_dense`` lays out a row-balanced mask."""
    from repro.core.bsr import tiles
    g = cfg.prune.group_size
    m = pruning.groups_kept_per_row(k, cfg.prune)
    block_n, lane, np_, mp, cp = tiles(n, k, g, m)
    items = np_ // block_n * (-(-m // lane))
    sds = jax.ShapeDtypeStruct
    bsr = BSRMatrix(
        words=sds((g // 8, np_, mp), jnp.int32),
        scale=sds((np_, mp), jnp.float32),
        zero=sds((np_, mp), jnp.float32),
        pos=sds((np_, cp), jnp.int32),
        work=sds((4, items), jnp.int32),
        shape=(n, k), group_size=g, bits=cfg.quant.bits, m=m,
        block_n=block_n, lane=lane)
    return {"bsr": bsr}


def dequant_dense(p: Dict, qcfg: Optional[QuantConfig] = None) -> jnp.ndarray:
    """Any representation -> dense FP weight (for tests / analysis). The
    W4 form returns its K padded to a multiple of 8·G (zero columns)."""
    from repro.core.bsr import to_dense
    if "bsr" in p:
        return to_dense(p["bsr"])
    if "qw" in p:
        return kref.w4_dense(p["qw"], p["scale"], p["zero"]).T
    if "gmask" in p:
        assert qcfg is not None
        w = fake_quant(p["w"], qcfg, p.get("scale"), p.get("zero"))
        return w * expand_mask(p["gmask"], qcfg.group_size).astype(w.dtype)
    return p["w"]
