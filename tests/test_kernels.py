"""Pallas kernels vs pure-jnp oracles: shape / dtype / sparsity sweeps in
interpret mode (CPU), per the assignment."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.bsr import pack_dense
from repro.core.gqs_layer import pack_w4
from repro.core.pruning import PruneConfig, group_mask
from repro.core.quant import QuantConfig
from repro.core.saliency import group_saliency
from repro.kernels import ops, ref


def _bsr_case(seed, n, k, g, sparsity, balanced=True, row_scale=None):
    rng = np.random.default_rng(seed)
    w = rng.normal(size=(n, k))
    if row_scale is not None:
        w = w * np.asarray(row_scale)[:, None]
    w = jnp.asarray(w, jnp.float32)
    gm = group_mask(group_saliency(jnp.square(w), g),
                    PruneConfig(sparsity=sparsity, group_size=g,
                                row_balanced=balanced))
    return w, pack_dense(w, gm, QuantConfig(bits=4, group_size=g))


# (136, 4608, 16) is tiled into two row blocks of 128 and, with more than
# 128 kept groups per row, two 128-slot chunks each: a multi-item work list.
@pytest.mark.parametrize("n,k,g", [(64, 128, 16), (96, 256, 16),
                                   (128, 128, 8), (32, 512, 32),
                                   (136, 4608, 16)])
@pytest.mark.parametrize("sparsity", [0.25, 0.5])
def test_gemv_shapes_sparsities(n, k, g, sparsity):
    w, bsr = _bsr_case(0, n, k, g, sparsity)
    x = jnp.asarray(np.random.default_rng(1).normal(size=(2, k)), jnp.float32)
    y_ref = ref.gqsa_gemv_ref(x, bsr)
    y_ker = ops.gqsa_gemv(x, bsr)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("balanced", [True, False])
def test_gemv_ragged_rows_task_centric(balanced):
    """Unbalanced (paper-faithful global-threshold) rows exercise the
    Stream-K-style work list with variable chunks per row block: the
    last 8 rows are scaled down, so they keep fewer groups and their
    row block needs one 128-slot chunk where the first needs two."""
    sparsity, chunks = (0.5, [2, 2]) if balanced else (0.6, [2, 1])
    n, k = 136, 4608
    w, bsr = _bsr_case(2, n, k, 16, sparsity, balanced=balanced,
                       row_scale=np.where(np.arange(n) < 128, 1.0, 0.7))
    row_block = np.asarray(bsr.work)[0]
    assert np.bincount(row_block).tolist() == chunks
    x = jnp.asarray(np.random.default_rng(3).normal(size=(1, k)),
                    jnp.float32)
    y_ref = ref.gqsa_gemv_ref(x, bsr)
    y_ker = ops.gqsa_gemv(x, bsr)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("xdtype", [jnp.float32, jnp.bfloat16])
def test_gemv_dtypes(xdtype):
    w, bsr = _bsr_case(4, 64, 128, 16, 0.5)
    x = jnp.asarray(np.random.default_rng(5).normal(size=(4, 128))).astype(
        xdtype)
    y_ref = ref.gqsa_gemv_ref(x, bsr)
    y_ker = ops.gqsa_gemv(x, bsr)
    np.testing.assert_allclose(np.asarray(y_ker, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_gemv_equals_dense_matmul_on_decompressed():
    w, bsr = _bsr_case(6, 64, 128, 16, 0.5)
    from repro.core.bsr import to_dense
    x = jnp.asarray(np.random.default_rng(7).normal(size=(3, 128)),
                    jnp.float32)
    y = ops.gqsa_gemv(x, bsr)
    y_dense = x @ to_dense(bsr).T
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_dense),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("t,n,k,g", [(8, 64, 128, 16), (16, 32, 256, 32),
                                     (64, 128, 128, 16)])
def test_w4_matmul_shapes(t, n, k, g):
    rng = np.random.default_rng(8)
    w = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    p = pack_w4(w, QuantConfig(bits=4, group_size=g))
    x = jnp.asarray(rng.normal(size=(t, k)), jnp.float32)
    y_ref = ref.w4_matmul_ref(x, p["qw"], p["scale"], p["zero"])
    y_ker = ops.w4_matmul(x, p["qw"], p["scale"], p["zero"])
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_w4_matmul_unaligned_shapes_padded():
    rng = np.random.default_rng(9)
    n, k, g, t = 48, 160, 16, 5
    w = jnp.asarray(rng.normal(size=(n, k)), jnp.float32)
    p = pack_w4(w, QuantConfig(bits=4, group_size=g))
    x = jnp.asarray(rng.normal(size=(t, k)), jnp.float32)
    y_ref = ref.w4_matmul_ref(x, p["qw"], p["scale"], p["zero"])
    y_ker = ops.w4_matmul(x, p["qw"], p["scale"], p["zero"])
    # K pads to a multiple of 8·G with zero codes and scales: the padded
    # columns contribute nothing
    w_deq = ref.w4_dense(p["qw"], p["scale"], p["zero"])
    assert w_deq.shape == (256, n) and not np.asarray(w_deq[k:]).any()
    np.testing.assert_allclose(np.asarray(y_ref),
                               np.asarray(x @ w_deq[:k]), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(y_ker), np.asarray(y_ref),
                               rtol=1e-4, atol=1e-4)


def test_bytes_models_monotone_in_sparsity():
    """fig6 premise: higher sparsity => fewer bytes => faster decode."""
    sizes = []
    for s in (0.2, 0.4, 0.6):
        _, bsr = _bsr_case(1, 128, 512, 16, s)
        sizes.append(ops.gemv_bytes_model(bsr)["total_bytes"])
    assert sizes[0] > sizes[1] > sizes[2]
    dense = ops.dense_bytes_model(128, 512, bits=16)["total_bytes"]
    w4 = ops.dense_bytes_model(128, 512, bits=4, group_size=16)["total_bytes"]
    assert dense > w4 > sizes[1]


@pytest.mark.parametrize("b,s,kh,r,d,bs", [(2, 128, 2, 4, 64, 32),
                                           (1, 256, 4, 2, 128, 64),
                                           (2, 96, 1, 8, 32, 32)])
def test_kv_decode_attention_kernel(b, s, kh, r, d, bs):
    """int8-KV decode attention kernel vs oracle (EXPERIMENTS §Perf cell C)."""
    from repro.kernels.ref import kv_decode_attention_ref
    from repro.models.layers import quantize_kv
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (b, kh, r, d))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, s, kh, d))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, s, kh, d))
    k_i8, k_sc = quantize_kv(k)
    v_i8, v_sc = quantize_kv(v)
    ln = jnp.int32(s - 17)
    o_ref = kv_decode_attention_ref(q, k_i8, k_sc, v_i8, v_sc, ln)
    o_ker = ops.kv_decode_attention(q, k_i8, k_sc, v_i8, v_sc, ln,
                                    block_s=bs)
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               rtol=1e-4, atol=1e-4)


def test_kv_decode_int8_close_to_fp_attention():
    """int8 cache quantization keeps attention outputs close to fp."""
    from repro.kernels.ref import kv_decode_attention_ref
    from repro.models.layers import decode_attention, quantize_kv
    rng = jax.random.PRNGKey(3)
    b, s, kh, r, d = 2, 64, 2, 4, 32
    q = jax.random.normal(rng, (b, 1, kh * r, d))
    k = jax.random.normal(jax.random.fold_in(rng, 1), (b, s, kh, d))
    v = jax.random.normal(jax.random.fold_in(rng, 2), (b, s, kh, d))
    o_fp = decode_attention(q, k, v, jnp.int32(s))
    k_i8, k_sc = quantize_kv(k)
    v_i8, v_sc = quantize_kv(v)
    # decode_attention groups H as (KH, R) kh-major — same layout as the
    # kernel's [B, KH, R, D]
    o_i8 = kv_decode_attention_ref(q.reshape(b, kh, r, d),
                                   k_i8, k_sc, v_i8, v_sc, jnp.int32(s))
    o_i8 = o_i8.reshape(b, 1, kh * r, d)
    assert float(jnp.max(jnp.abs(o_fp - o_i8))) < 0.05


def test_int8_cache_pallas_path_matches_jnp_in_model():
    """Model-level: the Pallas kv-decode kernel and the jnp int8 path agree
    through a full decode_step."""
    import dataclasses
    from repro.configs import get_config
    from repro.models.registry import get_model
    cfg = dataclasses.replace(get_config("llama2_7b", reduced=True),
                              kv_cache_dtype="int8")
    api = get_model(cfg)
    params = api.init_params(jax.random.PRNGKey(0), cfg)
    tok = jnp.zeros((2, 1), jnp.int32)

    def run(use_pallas):
        cache = api.init_cache(cfg, 2, 8)
        t, logs = tok, []
        for pos in range(3):
            lg, cache = api.decode_step(params, cache, t, jnp.int32(pos),
                                        cfg, use_pallas=use_pallas)
            logs.append(lg)
            t = jnp.argmax(lg[:, -1:, :], -1).astype(jnp.int32)
        return jnp.stack(logs)

    np.testing.assert_allclose(np.asarray(run(True)),
                               np.asarray(run(False)), atol=0.05)
