"""Grouped-dequant dense W4 matmul — Pallas TPU kernel.

The quantization-only baseline (paper's W4A16 rows): dequantize per-group
INT4 tiles in VMEM and feed the MXU.

Packed layout (``core/gqs_layer.py:pack_w4``, built at pack time). K is
padded to Kp, a multiple of 8·G, and split into eight *planes* of Kp/8
consecutive input columns; nibble i of word r holds the code of column
i·Kp/8 + r, so each nibble plane dequantizes to a contiguous row range of
W^T with no interleaving shuffle:

    x      [8, T, Kp/8]          activations, plane-major (a free reshape
                                 plus a transpose of x per call)
    qw     [Kp/8, N] int32       eight 4-bit codes per word
    scale  [8, Kp/(8G), N] f32   plane-major group scales
    zero   [8, Kp/(8G), N] f32
    y      [T, N]

Grid (T/BT, N/BN, Kp/(8·BR)): K innermost, accumulated in the revisited
out tile. BR word rows span BR/G groups of every plane.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_N = 128
DEFAULT_BLOCK_R = 512   # word rows per K step (8 x this many input columns)


def _kernel(x_ref, qw_ref, scale_ref, zero_ref, y_ref, *, group_size: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    words = qw_ref[...]                               # [BR, BN]
    br, bn = words.shape
    g = group_size
    acc = jnp.zeros(y_ref.shape, jnp.float32)
    for i in range(8):
        q = ((words >> (4 * i)) & 0xF).astype(jnp.float32)

        def rows(a):                                  # [BR/G, BN] -> [BR, BN]
            return jnp.broadcast_to(a[:, None, :], (br // g, g, bn)
                                    ).reshape(br, bn)
        w = (q - rows(zero_ref[i])) * rows(scale_ref[i])
        x = x_ref[i]                                  # [BT, BR]
        acc += jax.lax.dot_general(
            x, w.astype(x.dtype), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    y_ref[...] += acc


def w4_matmul_pallas(
    x: jnp.ndarray,
    qw: jnp.ndarray,
    scale: jnp.ndarray,
    zero: jnp.ndarray,
    *,
    group_size: int,
    block_t: int,
    block_n: int,
    block_r: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """x: [8, T, Kp/8] with T % block_t == 0; N % block_n == 0;
    (Kp/8) % block_r == 0; block_r % group_size == 0. Returns [T, N] f32."""
    _, t, r = x.shape
    n = qw.shape[1]
    g = group_size
    grid = (t // block_t, n // block_n, r // block_r)
    return pl.pallas_call(
        functools.partial(_kernel, group_size=g),
        grid=grid,
        in_specs=[
            pl.BlockSpec((8, block_t, block_r), lambda i, j, kk: (0, i, kk)),
            pl.BlockSpec((block_r, block_n), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((8, block_r // g, block_n),
                         lambda i, j, kk: (0, kk, j)),
            pl.BlockSpec((8, block_r // g, block_n),
                         lambda i, j, kk: (0, kk, j)),
        ],
        out_specs=pl.BlockSpec((block_t, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((t, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="w4_matmul",
    )(x, qw, scale, zero)
