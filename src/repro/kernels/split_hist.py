"""Decision-tree split-histogram kernel — the paper's dtree hotspot.

Builds H[node, feature, bin, class] counts for one level of CART growth.
The DPU version scatters scalar increments; the TPU version turns the
scatter into a one-hot matmul: for a block of rows and one feature at a
time, a (nodes*bins*classes, rows) one-hot of the combined index is
contracted against the row-weight vector on the MXU, accumulating
(F, nodes*bins*classes) partials in VMEM scratch across the sequential
row-block grid.  Rows run along lanes, so every input block and every
histogram row is lane-dense.

Rows carry a weight ``w`` (the PimGrid 0/1 row mask), so shard padding —
and the zero-padding used to round N up to a block multiple — adds
nothing to the histogram.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _hist_kernel(node_ref, xbin_ref, y_ref, w_ref, h_ref, acc, *,
                 n_nodes: int, n_bins: int, n_classes: int):
    i = pl.program_id(0)
    n = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)

    # rows run along lanes: every input block is a lane-dense (., bn) row
    node = node_ref[...]                          # (1, bn) int32
    y = y_ref[...]                                # (1, bn) int32
    w = w_ref[...].astype(jnp.float32)            # (1, bn)
    F, bn = xbin_ref.shape
    nbc = n_nodes * n_bins * n_classes
    ent = jax.lax.broadcasted_iota(jnp.int32, (nbc, bn), 0)

    # one feature at a time keeps a single (nbc, bn) one-hot live
    def feature(f, carry):
        xbin = xbin_ref[pl.ds(f, 1), :]           # (1, bn) int32
        # combined (node, bin, class) index per row
        comb = (node * n_bins + xbin) * n_classes + y
        onehot = (ent == comb).astype(jnp.float32)          # (nbc, bn)
        # contract rows on the MXU, each row weighted by its mask:
        # (1, bn) x (nbc, bn)^T -> a lane-dense (1, nbc) histogram row
        part = jax.lax.dot_general(
            w, onehot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc[pl.ds(f, 1), :] += part
        return carry

    jax.lax.fori_loop(0, F, feature, 0)

    @pl.when(i == n - 1)
    def _done():
        h_ref[...] = acc[...]


def split_hist(node_idx: jax.Array, xbin: jax.Array, y: jax.Array,
               w: jax.Array | None = None, *,
               n_nodes: int, n_bins: int, n_classes: int,
               block_n: int = 512, interpret: bool = False) -> jax.Array:
    """node_idx (N,), xbin (N,F), y (N,), w optional (N,) row weights ->
    H (n_nodes, F, n_bins, n_classes) f32.  N is zero-padded (with w=0)
    to a block multiple, so any N works.  On the TPU ``block_n`` must
    be a multiple of 128 or cover all of N (rows run along lanes)."""
    N, F = xbin.shape
    bn = min(block_n, N)
    nbc = n_nodes * n_bins * n_classes
    if w is None:
        w = jnp.ones((N,), jnp.float32)
    pad = -N % bn
    if pad:
        node_idx = jnp.pad(node_idx, (0, pad))
        xbin = jnp.pad(xbin, ((0, pad), (0, 0)))
        y = jnp.pad(y, (0, pad))
        w = jnp.pad(w, (0, pad))
    Np = N + pad

    kernel = functools.partial(_hist_kernel, n_nodes=n_nodes,
                               n_bins=n_bins, n_classes=n_classes)
    row = pl.BlockSpec((1, bn), lambda i: (0, i))
    h = pl.pallas_call(
        kernel,
        grid=(Np // bn,),
        in_specs=[row, pl.BlockSpec((F, bn), lambda i: (0, i)), row, row],
        out_specs=pl.BlockSpec((F, nbc), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((F, nbc), jnp.float32),
        scratch_shapes=[pltpu.VMEM((F, nbc), jnp.float32)],
        interpret=interpret,
        name="split_hist",
        metadata={"kernel": "split_hist"},
    )(node_idx.astype(jnp.int32)[None, :], xbin.astype(jnp.int32).T,
      y.astype(jnp.int32)[None, :], w[None, :])
    # (F, nodes*bins*classes) -> (nodes, F, bins, classes)
    return h.reshape(F, n_nodes, n_bins, n_classes).transpose(1, 0, 2, 3)
