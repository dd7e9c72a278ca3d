"""Fused K-means assignment kernel — the paper's K-means hotspot on TPU.

One pass over a block of points computes distances (MXU), argmin (VPU),
and the one-hot-matmul partial accumulation of per-cluster sums / counts
/ SSE (MXU) — the DPU's streaming point loop re-tiled for VMEM.  The
grid walks point blocks sequentially; partial statistics accumulate in
f32 VMEM scratch and are emitted at the last block (outputs map every
grid step to block 0, the canonical Pallas accumulator pattern).

Each point carries a weight ``w`` (the PimGrid row mask: 1 for real rows,
0 for shard padding) that scales its contribution to sums/counts/SSE —
this is what lets the kernel consume ``shard_rows`` output directly and
lets non-block-aligned N be zero-padded without contaminating the merge.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _km_kernel(x_ref, c_ref, w_ref, sums_ref, counts_ref, sse_ref,
               acc_s, acc_c, acc_e):
    i = pl.program_id(0)
    n = pl.num_programs(0)

    @pl.when(i == 0)
    def _init():
        acc_s[...] = jnp.zeros_like(acc_s)
        acc_c[...] = jnp.zeros_like(acc_c)
        acc_e[...] = jnp.zeros_like(acc_e)

    x = x_ref[...].astype(jnp.float32)               # (bn, D)
    c = c_ref[...].astype(jnp.float32)               # (K, D)
    w = w_ref[...].astype(jnp.float32)               # (bn, 1)
    # full f32 matmuls: SSE = |c|² − 2·x·c + |x|² cancels, so a
    # single-pass bf16 product would shift it by far more than f32 noise
    hi = jax.lax.Precision.HIGHEST
    xc = jax.lax.dot_general(x, c, (((1,), (1,)), ((), ())),
                             precision=hi,
                             preferred_element_type=jnp.float32)
    c2 = jnp.sum(c * c, axis=1)
    d = c2[None, :] - 2.0 * xc                       # (bn, K) (+||x||²)
    a = jnp.argmin(d, axis=1)
    K = c.shape[0]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], K), 1)
              == a[:, None]).astype(jnp.float32) * w
    acc_s[...] += jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())), precision=hi,
        preferred_element_type=jnp.float32)          # (K, D)
    acc_c[...] += jnp.sum(onehot, axis=0, keepdims=True)
    # SSE stays a (1, 1) vector end to end: Mosaic cannot store a
    # scalar into VMEM
    best = jnp.min(d, axis=1, keepdims=True)         # (bn, 1)
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    acc_e[...] += jnp.sum((best + x2) * w, axis=0, keepdims=True)

    @pl.when(i == n - 1)
    def _done():
        sums_ref[...] = acc_s[...]
        counts_ref[...] = acc_c[...]
        sse_ref[...] = acc_e[...]


def kmeans_assign(x: jax.Array, centroids: jax.Array,
                  w: jax.Array | None = None, *,
                  block_n: int = 1024,
                  interpret: bool = False):
    """x: (N, D) f32, centroids: (K, D), w: optional (N,) row weights ->
    (sums (K,D), counts (K,), sse ()).  N is zero-padded (with w=0) to a
    block multiple, so any N works."""
    N, D = x.shape
    K = centroids.shape[0]
    bn = min(block_n, N)
    if w is None:
        w = jnp.ones((N,), jnp.float32)
    pad = -N % bn
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        w = jnp.pad(w, (0, pad))
    Np = N + pad

    sums, counts, sse = pl.pallas_call(
        _km_kernel,
        grid=(Np // bn,),
        in_specs=[
            pl.BlockSpec((bn, D), lambda i: (i, 0)),
            pl.BlockSpec((K, D), lambda i: (0, 0)),   # VMEM-resident
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((K, D), lambda i: (0, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((K, D), jnp.float32),
            jax.ShapeDtypeStruct((1, K), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((K, D), jnp.float32),
            pltpu.VMEM((1, K), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret,
        name="kmeans_assign",
        metadata={"kernel": "kmeans_assign"},
    )(x, centroids, w[:, None])
    return sums, counts[0], sse[0, 0]
