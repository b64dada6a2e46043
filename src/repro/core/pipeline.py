"""The full GQSA compression pipeline (paper Figure 2):

    FP model --calibrate--> masks --BQPO--> fake-quant weights
             --freeze INT--> frozen codes --E2E-OQP--> tuned (s, z)
             --pack--> BSR serving params

One call: ``gqsa_compress(params, batches, cfg, gqsa)``. Dense family gets
exact per-linear Hessian calibration; packing preserves the E2E-tuned
scale/zero bit-exactly (verified by tests/test_gqsa_pipeline.py).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bqpo import BQPOConfig, bqpo
from repro.core.bsr import pack_quantized, stack_bsr
from repro.core.e2e_oqp import E2EConfig, e2e_oqp, freeze_int
from repro.core.gqs_layer import GQSAConfig


def pack_frozen(params_frozen: Dict) -> Dict:
    """frozen-int tree -> packed-BSR serving tree."""
    def walk(node):
        if isinstance(node, dict) and "q" in node and "gmask" in node:
            q = np.asarray(node["q"])
            gm = np.asarray(node["gmask"])
            sc = np.asarray(node["scale"])
            zr = np.asarray(node["zero"])
            lead = q.shape[:-2]
            n, k = q.shape[-2:]
            g = k // sc.shape[-1]
            qf = q.reshape((-1, n, k))
            gmf = gm.reshape((-1,) + gm.shape[-2:])
            scf = sc.reshape((-1,) + sc.shape[-2:])
            zrf = zr.reshape((-1,) + zr.shape[-2:])
            packed = [pack_quantized(jnp.asarray(qf[i]), gmf[i],
                                     jnp.asarray(scf[i]), jnp.asarray(zrf[i]),
                                     group_size=g)
                      for i in range(qf.shape[0])]
            return {"bsr": stack_bsr(packed, lead) if lead else packed[0]}
        if isinstance(node, dict):
            return {k: walk(v) for k, v in node.items()}
        return node
    return walk(params_frozen)


def gqsa_compress(params: Dict, token_batches: List[Dict], cfg,
                  gqsa: Optional[GQSAConfig] = None,
                  bqpo_cfg: Optional[BQPOConfig] = None,
                  e2e_cfg: Optional[E2EConfig] = None,
                  verbose: bool = False) -> Tuple[Dict, Dict]:
    """Returns (packed serving params, report)."""
    gqsa = gqsa or GQSAConfig()
    report = {}

    # stage 1: block-wise (calibration + masks happen inside, per block)
    toks = [b["tokens"] for b in token_batches]
    params_fq, block_losses = bqpo(params, toks, cfg, gqsa, bqpo_cfg,
                                   verbose=verbose)
    report["bqpo_block_mse"] = block_losses

    # freeze to INT codes
    params_frozen = freeze_int(params_fq, gqsa)

    # stage 2: end-to-end (s, z) fine-tune
    params_frozen, e2e_losses = e2e_oqp(params_frozen, token_batches, cfg,
                                        e2e_cfg, verbose=verbose)
    report["e2e_loss"] = e2e_losses

    packed = pack_frozen(params_frozen)
    return packed, report


def stage1_only(params: Dict, token_batches: List[Dict], cfg,
                gqsa: Optional[GQSAConfig] = None,
                bqpo_cfg: Optional[BQPOConfig] = None) -> Dict:
    """BQPO-only packed model (the paper's Table 6 ablation arm)."""
    gqsa = gqsa or GQSAConfig()
    toks = [b["tokens"] for b in token_batches]
    params_fq, _ = bqpo(params, toks, cfg, gqsa, bqpo_cfg)
    return pack_frozen(freeze_int(params_fq, gqsa))


def oneshot(params: Dict, token_batches: List[Dict], cfg,
            gqsa: Optional[GQSAConfig] = None) -> Dict:
    """No optimization at all: calibrate -> prune -> quantize -> pack
    (the 'naive GQSA' baseline)."""
    from repro.core.bqpo import (block_to_fake_quant, calibrate_block_stats,
                                 capture_block_io)
    gqsa = gqsa or GQSAConfig()
    ins = [capture_block_io(params, b["tokens"], cfg)[0]
           for b in token_batches]
    new_layers = []
    for l in range(cfg.n_layers):
        lp = jax.tree_util.tree_map(lambda a: a[l], params["layers"])
        stats = calibrate_block_stats(lp, [hi[l] for hi in ins], cfg)
        new_layers.append(block_to_fake_quant(lp, stats, gqsa))
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *new_layers)
    out = dict(params)
    out["layers"] = stacked
    return pack_frozen(freeze_int(out, gqsa))
