"""Whole-model GQSA compression: walk a parameter tree and replace every
eligible linear's {"w"} with the packed-BSR serving representation.

Eligible = the decode-path GEMV weights (attention projections, MLP /
expert FFNs, SSM in/out projections, MLA low-rank projections). Excluded =
embeddings, lm_head (kept FP16 as deployed engines do), norms, MLA w_uk/w_uv
(einsum-form, DESIGN.md §6), conv/ssm scalars, routers.

Handles weight stacking: leaves may be [L, N, K] (scan layers) or
[L, E, N, K] (scan layers x experts) — each 2-D slice is packed and the BSR
leaves are re-stacked, so the scan-based model code slices them layer by
layer exactly like dense weights.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bsr import BSRMatrix, pack_dense, stack_bsr
from repro.core.gqs_layer import GQSAConfig, packed_linear_shapes, pack_w4
from repro.core.pruning import PruneConfig, group_mask
from repro.core.quant import QuantConfig, group_minmax_params, quantize, \
    pack_int4
from repro.core.saliency import HessianStats, group_saliency, weight_saliency

COMPRESSIBLE = re.compile(
    r"(wq|wk|wv|wo|wg|wu|wd|w_qa|w_qb|w_kva|in_proj|out_proj)$")
EXCLUDED = re.compile(r"(router|shared_?$)")  # routers stay FP


def _path_str(path) -> str:
    parts = []
    for e in path:
        parts.append(str(getattr(e, "key", getattr(e, "idx", e))))
    return ".".join(parts)


def _is_compressible(pstr: str) -> bool:
    return bool(COMPRESSIBLE.search(pstr)) and not EXCLUDED.search(pstr)


def _walk(node, path, fn):
    """Replace {"w": leaf} dicts at compressible paths via fn(pstr, leaf)."""
    if isinstance(node, dict):
        if set(node.keys()) >= {"w"} and len(node) <= 2 and \
                _is_compressible(path):
            return fn(path, node)
        return {k: _walk(v, f"{path}.{k}" if path else k, fn)
                for k, v in node.items()}
    return node


def _pack_stacked(w: np.ndarray, cfg: GQSAConfig,
                  sal_fn: Optional[Callable] = None) -> BSRMatrix:
    """w: [..., N, K] -> BSRMatrix with leading dims stacked on each leaf."""
    lead = w.shape[:-2]
    n, k = w.shape[-2:]
    flat = w.reshape(-1, n, k)
    packed = []
    for i in range(flat.shape[0]):
        wi = jnp.asarray(flat[i])
        sal = sal_fn(wi) if sal_fn is not None else _magnitude_sal(wi)
        gsal = group_saliency(sal, cfg.prune.group_size)
        gm = group_mask(gsal, cfg.prune)
        packed.append(pack_dense(wi, gm, cfg.quant))
    return stack_bsr(packed, lead) if lead else packed[0]


# HessianStats has no _replace_uniform; provide magnitude fallback directly
def _magnitude_sal(w: jnp.ndarray) -> jnp.ndarray:
    return jnp.square(w.astype(jnp.float32))


def compress_params(params, cfg, gqsa: GQSAConfig,
                    stats: Optional[Dict[str, HessianStats]] = None):
    """FP param tree -> serving tree with packed GQS layers.

    ``stats`` maps path-string -> HessianStats (exact calibration). Layers
    without stats fall back to magnitude saliency (documented approximation
    for stacked/looped layers; the BQPO/E2E stages recover the gap).
    """
    def fn(pstr, node):
        w = node["w"]
        st = (stats or {}).get(pstr)
        if st is not None:
            sal_fn = lambda wi: weight_saliency(wi, st)
        else:
            sal_fn = _magnitude_sal
        return {"bsr": _pack_stacked(np.asarray(w), gqsa, sal_fn)}

    return _walk(params, "", fn)


def compress_params_w4(params, cfg, qcfg: QuantConfig):
    """Quantization-only baseline (dense W4, no pruning)."""
    def fn(pstr, node):
        w = node["w"]
        lead = w.shape[:-2]
        n, k = w.shape[-2:]
        flat = jnp.reshape(w, (-1, n, k))
        packs = [pack_w4(flat[i], qcfg) for i in range(flat.shape[0])]
        if not lead:
            return packs[0]
        stack = lambda *xs: jnp.stack(xs).reshape(lead + xs[0].shape)
        return jax.tree_util.tree_map(stack, *packs)
    return _walk(params, "", fn)


# ---------------------------------------------------------------------------
# Draft profiles (speculative decoding, DESIGN.md §4): one FP checkpoint
# yields BOTH the deployed target compression and a more aggressive draft
# compression. Quality collapse at draft-level settings is fine — the
# engine's verify step makes the served distribution exactly the target's,
# so the draft profile only trades acceptance rate against draft cost.
# ---------------------------------------------------------------------------

DRAFT_PROFILES: Dict[str, Dict] = {
    # dense 4-bit (no pruning): near-target quality, highest acceptance
    "w4": dict(bits=4, sparsity=0.0),
    # the paper's deployed setting — as a draft it accepts ~everything
    "w4s50": dict(bits=4, sparsity=0.5),
    # aggressive: settings the paper shows degrade too much to SERVE,
    # which is exactly what a drafter is allowed to be
    "w4s75": dict(bits=4, sparsity=0.75),
    "w2s50": dict(bits=2, sparsity=0.5),
    "w2s75": dict(bits=2, sparsity=0.75),
    # depth-pruned (LayerSkip-style self-speculation): keep the first
    # 12.5% / 25% / 50% of layers — sparsity at LAYER granularity, the
    # knob that makes a draft step structurally cheaper in every cost
    # regime (the shallow exit shares the final norm + unembedding)
    "w4l12": dict(bits=4, sparsity=0.0, depth=0.125),
    "w4l25": dict(bits=4, sparsity=0.0, depth=0.25),
    "w4l50": dict(bits=4, sparsity=0.0, depth=0.5),
    "w4s50l50": dict(bits=4, sparsity=0.5, depth=0.5),
}


def draft_layers(cfg, profile: str) -> int:
    """Effective drafter depth for a profile (>= 1, full when no depth)."""
    try:
        spec = DRAFT_PROFILES[profile]
    except KeyError:
        raise ValueError(f"unknown draft profile {profile!r}; "
                         f"known: {sorted(DRAFT_PROFILES)}")
    depth = spec.get("depth", 1.0)
    return max(1, int(round(cfg.n_layers * depth)))


def compress_draft(params, cfg, profile: str = "w4s75",
                   group_size: int = 16,
                   stats: Optional[Dict[str, HessianStats]] = None):
    """FP param tree -> the draft-profile parameter set.

    ``params`` is the SAME checkpoint the target compression starts
    from. Depth profiles first truncate the stacked layer leaves to the
    profile's layer count (embed / final norm / lm_head stay shared);
    then sparsity 0 routes to the dense W4 packer, anything else to the
    full GQSA packer at the profile's (bits, sparsity). A depth-pruned
    draft must be RUN at ``draft_layers(cfg, profile)`` layers
    (the engine's ``EngineConfig.spec_draft_layers``).
    """
    dl = draft_layers(cfg, profile)          # validates the profile name
    spec = DRAFT_PROFILES[profile]
    if dl < cfg.n_layers:
        params = dict(params, layers=jax.tree_util.tree_map(
            lambda l: l[:dl], params["layers"]))
    if spec["sparsity"] <= 0.0:
        return compress_params_w4(params, cfg, QuantConfig(
            bits=spec["bits"], group_size=group_size))
    gqsa = GQSAConfig(
        quant=QuantConfig(bits=spec["bits"], group_size=group_size),
        prune=PruneConfig(sparsity=spec["sparsity"], group_size=group_size))
    return compress_params(params, cfg, gqsa, stats=stats)


def compress_params_shapes(params_template, cfg, gqsa: GQSAConfig):
    """ShapeDtypeStruct version for the dry-run (no data, no loops)."""
    def fn(pstr, node):
        w = node["w"]
        lead = w.shape[:-2]
        n, k = w.shape[-2:]
        base = packed_linear_shapes(n, k, gqsa)["bsr"]

        def lift(l):
            return jax.ShapeDtypeStruct(lead + l.shape, l.dtype)
        leaves, treedef = jax.tree_util.tree_flatten(base)
        return {"bsr": treedef.unflatten([lift(l) for l in leaves])}

    return _walk(params_template, "", fn)


def compression_report(fp_params, packed_params) -> dict:
    def nbytes(t):
        return sum(np.prod(l.shape) * jnp.dtype(l.dtype).itemsize
                   for l in jax.tree_util.tree_leaves(t))
    fp = float(nbytes(fp_params))
    pk = float(nbytes(packed_params))
    return {"fp32_bytes": fp, "fp16_bytes": fp / 2, "packed_bytes": pk,
            "ratio_vs_fp16": (fp / 2) / pk}
