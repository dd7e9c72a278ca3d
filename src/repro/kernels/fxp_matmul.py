"""Fixed-point (int8 x int8 -> int32) tiled matmul Pallas TPU kernel —
the paper's insight I1 on the MXU's native s8 path.

Grid (M/bm, N/bn, K/bk): the K dimension is the sequential minor grid
axis; partial products accumulate in an int32 VMEM scratch tile (the
paper's hybrid precision: narrow multiply, wide accumulate).  Block
shapes are MXU-aligned (multiples of 128 on the minor dims).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fxp_kernel(a_ref, b_ref, o_ref, acc_ref):
    kk = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kk == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    a = a_ref[...]                                   # (bm, bk) int8
    b = b_ref[...]                                   # (bk, bn) int8
    acc_ref[...] += jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)

    @pl.when(kk == nk - 1)
    def _done():
        o_ref[...] = acc_ref[...]


def _pad_axis(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    """Zero-pad ``axis`` of ``x`` up to the next multiple of ``multiple``."""
    size = x.shape[axis]
    pad = -size % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def fxp_matmul(a: jax.Array, b: jax.Array, *, block_m: int = 256,
               block_n: int = 256, block_k: int = 512,
               interpret: bool = False) -> jax.Array:
    """a: (M, K) int8, b: (K, N) int8 -> (M, N) int32.

    Non-block-aligned shapes are zero-padded up to block multiples and the
    result sliced back — zero padding is exact for integer matmul.
    """
    M, K = a.shape
    K2, N = b.shape
    assert K == K2
    bm, bn, bk = min(block_m, M), min(block_n, N), min(block_k, K)
    a = _pad_axis(_pad_axis(a, 0, bm), 1, bk)
    b = _pad_axis(_pad_axis(b, 0, bk), 1, bn)
    Mp, Kp = a.shape
    Np = b.shape[1]

    out = pl.pallas_call(
        _fxp_kernel,
        grid=(Mp // bm, Np // bn, Kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=interpret,
        name="fxp_matmul",
        metadata={"kernel": "fxp_matmul"},
    )(a, b)
    return out[:M, :N]
