"""The main-path Pallas kernels compile for one TPU v5e chip at
starcoder2-3b widths (and the DeepSeek latent head width).

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
from repro.kernels.gqsa_gemv import gqsa_gemv_pallas
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
