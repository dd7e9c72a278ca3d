"""LUT activation Pallas TPU kernel — the paper's insight I2, TPU-native.

The DPU version gathers scalar table entries from WRAM.  A systolic
machine wants matrix work, so the kernel evaluates the lookup as
``one_hot(idx, n_entries) @ table`` on the MXU with the table resident in
VMEM — a (block, n_entries) x (n_entries, 1) matmul per tile.  For
256-1024-entry tables this is cheaper than computing exp/div on the VPU
and exactly reproduces nearest-entry LUT semantics (error bound tested in
tests/test_kernels.py against core.lut).

Input tiles stream HBM->VMEM as (block_rows, lane) blocks (insight I3:
every access is a contiguous burst).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _lut_kernel(x_ref, table_ref, o_ref, *, x_min: float, step: float,
                n_entries: int):
    x = x_ref[...].astype(jnp.float32)              # (bm, bn)
    idx = jnp.clip(jnp.round((x - x_min) / step), 0, n_entries - 1
                   ).astype(jnp.int32)
    bm, bn = x.shape
    # one-hot(idx) @ table on the MXU (TPU-native gather)
    ent = jax.lax.broadcasted_iota(jnp.int32, (bm, bn, n_entries), 2)
    onehot = (ent == idx[..., None]).astype(jnp.float32)
    tab = table_ref[...].astype(jnp.float32)        # (n_entries,)
    # HIGHEST: the one-hot picks the f32 table entry exactly, where a
    # single bf16 pass would round it
    out = jax.lax.dot_general(
        onehot.reshape(bm * bn, n_entries), tab[:, None],
        (((1,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[:, 0]
    o_ref[...] = out.reshape(bm, bn).astype(o_ref.dtype)


# the kernel builds a (bm, bn, n_entries) f32 one-hot per block (plus the
# iota it compares against): 4 MiB of it leaves room in the TPU's 16 MiB
# default scoped VMEM
_ONEHOT_ELEMS = 1 << 20


def _onehot_bounded_blocks(M: int, N: int, n_entries: int,
                           block_rows: int, block_cols: int):
    """Shrink ``(block_rows, block_cols)`` until the block's one-hot fits
    ``_ONEHOT_ELEMS``, rows first, keeping the (8, 128) tiling of a
    block that does not cover its whole axis.  The (8, 128) floor holds
    even where it exceeds the budget (tables above 1,024 entries)."""
    bm, bn = min(block_rows, M), min(block_cols, N)
    while bm * bn * n_entries > _ONEHOT_ELEMS and bm > 8:
        bm = max(8, bm // 16 * 8)
    while bm * bn * n_entries > _ONEHOT_ELEMS and bn > 128:
        bn = max(128, bn // 256 * 128)
    return bm, bn


def lut_activation(x: jax.Array, table: jax.Array, *, x_min: float,
                   x_max: float, block_rows: int = 256,
                   block_cols: int = 512,
                   interpret: bool = False) -> jax.Array:
    """Elementwise LUT evaluation (any rank; flattened to 2D internally).

    Non-block-aligned shapes are zero-padded to block multiples and the
    result sliced back (the LUT of the pad values is simply discarded)."""
    orig_shape = x.shape
    x2 = jnp.atleast_1d(x).reshape(-1, orig_shape[-1] if orig_shape else 1)
    M, N = x2.shape
    n_entries = table.shape[0]
    bm, bn = _onehot_bounded_blocks(M, N, n_entries, block_rows,
                                    block_cols)
    pad_m, pad_n = -M % bm, -N % bn
    if pad_m or pad_n:
        x2 = jnp.pad(x2, ((0, pad_m), (0, pad_n)))
    Mp, Np = x2.shape
    step = (x_max - x_min) / (n_entries - 1)

    kernel = functools.partial(_lut_kernel, x_min=x_min, step=step,
                               n_entries=n_entries)
    out = pl.pallas_call(
        kernel,
        grid=(Mp // bm, Np // bn),
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((n_entries,), lambda i, j: (0,)),  # VMEM-resident
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Mp, Np), x.dtype),
        interpret=interpret,
        name="lut_activation",
        metadata={"kernel": "lut_activation"},
    )(x2, table)
    return out[:M, :N].reshape(orig_shape)
