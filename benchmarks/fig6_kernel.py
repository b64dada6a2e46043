"""Paper Figure 6: GEMV kernel speed vs sparsity and group size.

No TPU here, so two views are reported per point:
  * measured CPU wall-clock of the jitted XLA reference path (relative
    ordering: higher sparsity => fewer bytes => faster), and
  * the derived TPU byte-traffic model (kernels/ops.gemv_bytes_model) +
    v5e HBM roofline time — the quantity the paper's figure actually tracks,
    since decode GEMV is bandwidth-bound.
"""
import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, time_call
from repro.core.bsr import pack_dense
from repro.core.gqs_layer import pack_w4
from repro.core.pruning import PruneConfig, group_mask
from repro.core.quant import QuantConfig
from repro.core.saliency import group_saliency
from repro.kernels import ops, ref
from repro.launch.hlo_analysis import HBM_BW

N = K = 1024  # paper uses 4096x4096; scaled for CPU wall-clock


def main():
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.normal(size=(N, K)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(1, K)), jnp.float32)

    # dense fp baseline
    dense = jax.jit(lambda xx: xx @ w.T)
    us = time_call(dense, x)
    bts = ops.dense_bytes_model(N, K, bits=16)
    emit("fig6/fp16_dense", us,
         f"tpu_us={bts['total_bytes']/HBM_BW*1e6:.2f};"
         f"bytes={bts['total_bytes']}")

    # W4 dense baseline
    p4 = pack_w4(w, QuantConfig(bits=4, group_size=16))
    w4 = jax.jit(lambda xx: ref.w4_matmul_ref(xx, p4["qw"], p4["scale"],
                                              p4["zero"]))
    us = time_call(w4, x)
    bts = ops.dense_bytes_model(N, K, bits=4, group_size=16)
    emit("fig6/w4_dense", us,
         f"tpu_us={bts['total_bytes']/HBM_BW*1e6:.2f};"
         f"bytes={bts['total_bytes']}")

    for g in (8, 16, 32):
        for sp in (0.25, 0.5, 0.75):
            gm = group_mask(group_saliency(jnp.square(w), g),
                            PruneConfig(sparsity=sp, group_size=g))
            bsr = pack_dense(w, gm, QuantConfig(bits=4, group_size=g))
            f = jax.jit(lambda xx: ref.gqsa_gemv_ref(xx, bsr))
            us = time_call(f, x)
            bts = ops.gemv_bytes_model(bsr)
            emit(f"fig6/gqsa_g{g}_s{int(sp*100)}", us,
                 f"tpu_us={bts['total_bytes']/HBM_BW*1e6:.2f};"
                 f"bytes={bts['total_bytes']}")


if __name__ == "__main__":
    main()
