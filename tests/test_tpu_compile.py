"""The main-path Pallas kernels compile for one TPU v5e chip at
starcoder2-3b widths (and the DeepSeek latent head width, and Mistral-Nemo's
K 5120 for the prefill densify pass).

Nothing runs: each kernel is lowered and compiled for a chip that is
described, not attached, so the TPU compiler refuses here whatever it
would refuse on the chip (untileable blocks, unsupported casts, gathers or
reshapes, too much VMEM). The topology is described inside a fixture,
never at import: only the worker that runs this file loads the TPU
library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import bsr as bsr_mod
from repro.kernels.gqsa_gemv import gqsa_densify_pallas, gqsa_gemv_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.w4_matmul import w4_matmul_pallas

# starcoder2-3b: d_model 3072, d_ff 12288, 24 heads / 2 KV heads of 128
D_MODEL, D_FF, KV_HEADS, HEAD_DIM, GROUPS = 3072, 12288, 2, 128, 12
G, PAGE, POOL_PAGES, MAX_PAGES, BATCH = 16, 16, 512, 40, 8


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("n,k", [(D_MODEL, D_MODEL), (D_FF, D_MODEL),
                                 (D_MODEL, D_FF), (KV_HEADS * HEAD_DIM,
                                                   D_MODEL)])
def test_gqsa_gemv_compiles_for_decode(one_chip, n, k):
    """Decode GEMV at batch 8, W4 S50 G16, in the pack-time layout."""
    m = k // G // 2
    block_n, lane, np_, mp, cp = bsr_mod.tiles(n, k, G, m)
    items = np_ // block_n * -(-m // lane)

    def fn(x, words, scale, zero, pos, work):
        return gqsa_gemv_pallas(x, words, scale, zero, pos, work,
                                group_size=G, block_n=block_n, lane=lane,
                                block_t=BATCH)
    _compile(fn, one_chip, ((BATCH, G * cp), jnp.bfloat16),
             ((G // 8, np_, mp), jnp.int32), ((np_, mp), jnp.float32),
             ((np_, mp), jnp.float32), ((np_, cp), jnp.int32),
             ((4, items), jnp.int32))


def test_gqsa_gemv_label_names_the_compiled_kernel(one_chip):
    """A linear's label reaches the kernel's name, the one name the
    chip's trace keeps for the op (``%gqsa_gemv_wk.N = ... custom-call``)."""
    n, k = KV_HEADS * HEAD_DIM, D_MODEL
    m = k // G // 2
    block_n, lane, np_, mp, cp = bsr_mod.tiles(n, k, G, m)
    items = np_ // block_n * -(-m // lane)

    def fn(x, words, scale, zero, pos, work):
        return gqsa_gemv_pallas(x, words, scale, zero, pos, work,
                                group_size=G, block_n=block_n, lane=lane,
                                block_t=BATCH, label="wk")
    text = _compile(fn, one_chip, ((BATCH, G * cp), jnp.bfloat16),
                    ((G // 8, np_, mp), jnp.int32), ((np_, mp), jnp.float32),
                    ((np_, mp), jnp.float32), ((np_, cp), jnp.int32),
                    ((4, items), jnp.int32)).as_text()
    assert "%gqsa_gemv_wk" in text


# starcoder2-3b's down projection and Mistral-Nemo's gate (K 5120: 320
# group columns pad to 384 lanes, and the unpadded tile is stored at
# unaligned lane offsets).
@pytest.mark.parametrize("n,k", [(D_MODEL, D_FF), (14336, 5120)])
def test_gqsa_densify_compiles(one_chip, n, k):
    """The prefill densify pass, named ``gqsa_densify_<label>`` in the
    compiled program as in the chip's trace."""
    m = k // G // 2
    block_n, lane, np_, mp, cp = bsr_mod.tiles(n, k, G, m)
    items = np_ // block_n * -(-m // lane)

    def fn(words, scale, zero, pos, work):
        return gqsa_densify_pallas(words, scale, zero, pos, work,
                                   group_size=G, block_n=block_n, lane=lane,
                                   c=k // G, dtype=jnp.bfloat16, label="wd")
    text = _compile(fn, one_chip, ((G // 8, np_, mp), jnp.int32),
                    ((np_, mp), jnp.float32), ((np_, mp), jnp.float32),
                    ((np_, cp), jnp.int32), ((4, items), jnp.int32)).as_text()
    assert "%gqsa_densify_wd" in text


@pytest.mark.parametrize("t,fused", [(32, True), (4096, False)])
def test_gqsa_linear_path_follows_row_count(one_chip, monkeypatch, t, fused):
    """One activation tile (decode) runs the fused kernel; a 4096-row
    prefill densifies the weight once and multiplies it outside."""
    from repro.core.gqs_layer import GQSAConfig, packed_linear_shapes
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    bsr = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip),
        packed_linear_shapes(D_MODEL, D_MODEL, GQSAConfig())["bsr"])
    x = jax.ShapeDtypeStruct((t, D_MODEL), jnp.bfloat16, sharding=one_chip)
    text = jax.jit(lambda x, b: ops.gqsa_gemv(x, b, "wq")).lower(
        x, bsr).compile().as_text()
    assert ("%gqsa_gemv_wq" in text) == fused
    assert ("%gqsa_densify_wq" in text) != fused


@pytest.mark.parametrize("n,k", [(D_MODEL, D_MODEL), (D_MODEL, D_FF)])
def test_w4_matmul_compiles_for_prefill_block(one_chip, n, k):
    """Dense W4 matmul on a 256-row prefill block."""
    from repro.kernels.ops import _block_r
    rows = k // 8

    def fn(x, qw, scale, zero):
        return w4_matmul_pallas(x, qw, scale, zero, group_size=G,
                                block_t=256, block_n=128,
                                block_r=_block_r(rows, G))
    _compile(fn, one_chip, ((8, 256, rows), jnp.bfloat16),
             ((rows, n), jnp.int32), ((8, rows // G, n), jnp.float32),
             ((8, rows // G, n), jnp.float32))


@pytest.mark.parametrize("t,tree", [(1, False), (5, False), (5, True)])
def test_paged_attention_compiles(one_chip, t, tree):
    """GQA decode (T = 1) and speculative verify (T = 5, chain staircase
    or token-tree ancestor bitmaps) on the head-major bf16 pool."""
    rows = -(-t * GROUPS // 8) * 8

    def fn(q, k, v, lengths, bt, live, anc, base):
        return paged_attention_pallas(
            q, k, v, lengths, bt, live, t=t, r=GROUPS,
            anc=anc if tree else None, anc_base=base if tree else None,
            anc_window=t if tree else 0)
    pool = ((KV_HEADS, POOL_PAGES, PAGE, HEAD_DIM), jnp.bfloat16)
    _compile(fn, one_chip, ((BATCH, KV_HEADS, rows, HEAD_DIM), jnp.bfloat16),
             pool, pool, ((BATCH, t), jnp.int32),
             ((BATCH, MAX_PAGES), jnp.int32), ((BATCH,), jnp.int32),
             ((BATCH, t), jnp.int32), ((BATCH,), jnp.int32))


@pytest.mark.parametrize("t", [1, 5])
def test_paged_latent_attention_compiles(one_chip, t):
    """MLA latent mode: one logical head of kv_lora_rank + rope = 576
    lanes, 16 query heads."""
    heads, d = 16, 576
    rows = -(-t * heads // 8) * 8

    def fn(q, lat, lengths, bt, live):
        return paged_attention_pallas(q, lat, None, lengths, bt, live, t=t,
                                      r=heads)
    _compile(fn, one_chip, ((BATCH, 1, rows, d), jnp.bfloat16),
             ((1, POOL_PAGES, PAGE, d), jnp.bfloat16),
             ((BATCH, t), jnp.int32), ((BATCH, MAX_PAGES), jnp.int32),
             ((BATCH,), jnp.int32))


def test_latent_and_gqa_kernels_carry_their_own_names(one_chip):
    """The latent mode compiles as ``paged_latent_attention`` and the GQA
    mode keeps ``paged_attention``, so a device trace tells them apart."""
    def text(latent):
        d, kh = (576, 1) if latent else (HEAD_DIM, KV_HEADS)
        rows = 16 if latent else GROUPS

        def fn(q, k, v, lengths, bt, live):
            return paged_attention_pallas(q, k, None if latent else v,
                                          lengths, bt, live, t=1, r=rows)
        pool = ((kh, POOL_PAGES, PAGE, d), jnp.bfloat16)
        return _compile(fn, one_chip, ((BATCH, kh, 16, d), jnp.bfloat16),
                        pool, pool, ((BATCH, 1), jnp.int32),
                        ((BATCH, MAX_PAGES), jnp.int32),
                        ((BATCH,), jnp.int32)).as_text()
    latent, gqa = text(True), text(False)
    assert "%paged_latent_attention" in latent
    assert "%paged_attention" in gqa and "paged_latent" not in gqa


# DeepSeek-V2-Lite's expert linears: 64 experts of [1408, 2048] (gate,
# up) and [2048, 1408] (down)
@pytest.mark.parametrize("n,k", [(1408, 2048), (2048, 1408)])
def test_expert_stack_kernels_compile(one_chip, n, k):
    """The fused GEMV over a 64-expert stack at a 32-row decode buffer
    and the stack's densify pass, each one kernel named by its linear."""
    from repro.core.gqs_layer import GQSAConfig, packed_linear_shapes
    from repro.kernels import ops
    bsr = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((64,) + l.shape, l.dtype,
                                       sharding=one_chip),
        packed_linear_shapes(n, k, GQSAConfig())["bsr"])
    x = jax.ShapeDtypeStruct((64, 32, k), jnp.bfloat16, sharding=one_chip)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(ops, "_interpret", lambda: False)
        gemv = jax.jit(lambda x, b: ops.gqsa_gemv_experts(x, b, "ewg")
                       ).lower(x, bsr).compile().as_text()
        dense = jax.jit(lambda b: ops.gqsa_densify_experts(
            b, jnp.bfloat16, "ewd")).lower(bsr).compile().as_text()
    assert "%gqsa_gemv_ewg" in gemv and "%gqsa_densify_ewd" in dense


def test_dsv2_lite_steps_compile_and_fit(one_chip, monkeypatch):
    """DeepSeek-V2-Lite at its benchmark cell's size (published widths,
    14 of 27 layers, 32 slots, max_seq 768): the decode step runs the
    expert GEMV over the stack and the latent kernel, the first fill's
    prefill (32 x 768 rows) densifies the experts once; both fit one
    chip by the compiler's count."""
    import json
    import sys
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [checkout]
    from bench import weights
    from bench.harness import engine_config, shapes
    from bench.models import mla_moe
    from repro.core.gqs_layer import GQSAConfig, packed_linear_shapes
    from repro.engine import SamplingParams
    from repro.engine.engine import _step_fns
    from repro.kernels import ops
    from repro.models.registry import get_model
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    with open(os.path.join(checkout, "bench", "configs",
                           "deepseek-v2-lite.gqsa.json")) as f:
        conf = json.load(f)
    with open(os.path.join(checkout, "bench", "traffic",
                           "decode_backlog.json")) as f:
        tr = json.load(f)
    model = mla_moe.Model.from_conf(conf)
    assert weights.Compression.from_conf(conf["compression"]) == \
        weights.Compression(4, 0.5, 16)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    packed = {}
    for block, name, _, n, k, lead in model.linears():
        leaves, tdef = jax.tree_util.tree_flatten(
            packed_linear_shapes(n, k, GQSAConfig())["bsr"])
        packed.setdefault(block, {})[name] = {"bsr": tdef.unflatten(
            [sds(tuple(lead) + l.shape, l.dtype) for l in leaves])}
    dense = jax.tree_util.tree_map(
        lambda l: sds(l.shape, l.dtype),
        jax.eval_shape(lambda: mla_moe.unpacked_leaves(model, 0,
                                                       jnp.bfloat16)))
    params = mla_moe.assemble(packed, dense)
    cfg, ecfg = mla_moe.program_config(conf), engine_config(tr)
    b = ecfg.num_slots
    mp = -(-ecfg.max_seq // ecfg.page_size)
    cache = jax.tree_util.tree_map(
        lambda l: sds(l.shape, l.dtype),
        jax.eval_shape(lambda: get_model(cfg).init_paged_cache(
            cfg, b * mp, ecfg.page_size)))

    def i32(*shape):
        return sds(shape, jnp.int32)
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    sh = shapes(tr, ecfg)
    _step_fns.cache_clear()
    try:
        prefill, decode, _ = _step_fns(cfg, SamplingParams(), True)
        dec = decode.lower(params, cache, i32(b), i32(b), i32(b, mp),
                           i32(b), rng, max(sh["decode"])).compile()
        pre = prefill.lower(params, cache, i32(b, sh["first_fill"][0]),
                            i32(b), i32(b, mp), rng).compile()
    finally:
        _step_fns.cache_clear()
    for what, comp, kernels in (
            ("decode", dec, ("%gqsa_gemv_ewg", "%gqsa_gemv_ewd",
                             "%paged_latent_attention")),
            ("prefill", pre, ("%gqsa_densify_ewu",))):
        ma = comp.memory_analysis()
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        print(f"deepseek-v2-lite x 14 layers {what}: arguments "
              f"{ma.argument_size_in_bytes / 2**30:.2f} GiB, temp "
              f"{ma.temp_size_in_bytes / 2**30:.2f} GiB, total "
              f"{total / 2**30:.2f} GiB")
        text = comp.as_text()
        assert all(kname in text for kname in kernels), what
        assert total < 15 * 2**30
