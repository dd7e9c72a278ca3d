"""Kernel dispatch: routes the mlalgos' inner loops to the Pallas kernels.

The paper's central claim is that PIM wins exactly when the necessary
operations and datatypes are natively supported by the hardware.  This
module is where that support is *selected*: each mlalgo hot spot calls a
dispatch function instead of inlining jnp, and the dispatch table decides
whether the native Pallas kernel or the pure-jnp reference runs.

Dispatch table (mlalgo hot spot -> Pallas kernel):

  ==================  ========================  =======================
  dispatch fn         kernel                    used by
  ==================  ========================  =======================
  ``hybrid_matmul``   ``fxp_matmul``            linreg/logreg int8/int16
                                                forward + gradient dots
  ``kmeans_partials`` ``kmeans_assign``         kmeans fused distance ->
                                                argmin -> accumulate
  ``level_histogram`` ``split_hist``            dtree per-level split
                                                statistics
  ``lut_apply``       ``lut_activation``        logreg LUT sigmoid
  ==================  ========================  =======================

Backend selection is automatic and made at trace time: on the TPU the
kernels lower to Mosaic; on CPU/GPU they run with ``interpret=True`` —
jnp emulation that stays jit/vmap-compatible, so the same mlalgo code
path is exercised everywhere.  ``use_kernels(False)`` flips every entry
to its pure-jnp reference; parity tests and the before/after benchmarks
use it.  The flag is read at *trace* time, so flipping it only affects
functions traced afterwards (each ``train_*`` call traces afresh).

All dispatch functions accept per-row weights where the underlying
statistic must ignore PimGrid shard padding, and every kernel pads
non-block-aligned shapes internally — callers never see alignment
constraints.

Block shapes are no longer fixed constants: every kernel call asks
``tuning.autotune.block_shapes`` for its tile sizes, keyed on
``(kernel, dtype, shape-bucket, backend)``.  Measured entries from the
autotune table (``tuning.autotune.cache_path``) win; otherwise a
per-backend heuristic applies (MXU-aligned VMEM-bounded tiles on TPU,
fewest-grid-steps blocks under interpret mode, where the kernel body
runs once per grid step).  See ``repro/tuning/autotune.py`` (``kernels/autotune.py`` is
a back-compat re-export).

Interaction with the scan engine's compile cache: ``PimGrid.make_runner``
reads ``kernels_enabled()`` at trace time and bakes it into its cache
key, so a runner traced inside ``use_kernels(False)`` never serves a
kernels-on fit (and vice versa).  Flip the flag *around* the ``train_*``
call, never across an already-compiled runner.

Example — the kernel path and the jnp reference agree exactly on an
integer matmul (int8 operands, int32 accumulation, float32 out):

>>> import jax.numpy as jnp
>>> from repro.kernels import dispatch
>>> a = jnp.ones((4, 8), jnp.int8)
>>> b = jnp.ones((8, 2), jnp.int8)
>>> out = dispatch.hybrid_matmul(a, b)
>>> out.shape, out.dtype
((4, 2), dtype('float32'))
>>> with dispatch.use_kernels(False):        # pure-jnp reference
...     ref = dispatch.hybrid_matmul(a, b)
>>> bool(jnp.array_equal(out, ref))
True
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

from repro.core import lut as lut_mod
from repro.core import quantize as qz
from repro.tuning import autotune as _at
from repro.kernels import fxp_matmul as _fxp
from repro.kernels import kmeans_assign as _km
from repro.kernels import lut_activation as _lut
from repro.kernels import ref as _ref
from repro.kernels import split_hist as _sh
from repro.kernels.ops import interpret_mode

_ENABLED = [True]
_LANES = 128                # minor dimension of a TPU vector tile


def kernels_enabled() -> bool:
    """True when dispatch routes to the Pallas kernels (trace-time flag)."""
    return _ENABLED[0]


@contextlib.contextmanager
def use_kernels(enabled: bool):
    """Temporarily force the Pallas path on/off (for parity tests and
    before/after benchmarks)."""
    prev = _ENABLED[0]
    _ENABLED[0] = enabled
    try:
        yield
    finally:
        _ENABLED[0] = prev


# ---------------------------------------------------------------------------
# hybrid_matmul — linreg/logreg int8/int16 dots on the fxp_matmul kernel
# ---------------------------------------------------------------------------

def hybrid_matmul(a: jax.Array, b: jax.Array, *,
                  k_chunk: int = 4096,
                  backend: str | None = None) -> jax.Array:
    """Drop-in for ``quantize.hybrid_dot`` at the mlalgos call sites:
    (M, K) int8/int16 x (K, N) int8/int16 -> (M, N) float32.

    Each operand splits into signed int8 limbs (``quantize.int8_limbs``).
    ``b``'s limbs sit side by side in one ``(K, limbs*N)`` operand, so
    each limb of ``a`` is one ``fxp_matmul`` pass over the data.  Limb
    products accumulate in int32 over K-chunks of ``k_chunk``
    (|limb product| <= 2^14, so each chunk partial stays below
    2^26 < 2^31); chunk/limb partials combine in float32 in
    ``hybrid_dot``'s order — bit-identical to it, exact for any K.

    A tall, narrow product (a matvec's ``(rows, 1)``) is computed
    transposed, ``(b^T a^T)^T``, so the kernel writes its long side
    along the 128 lanes of a TPU tile instead of padding a handful of
    columns out to 128.

    ``backend`` is the backend the kernel is built for (default: JAX's
    default backend): Mosaic on ``"tpu"``, interpret mode elsewhere.
    Naming ``"tpu"`` compiles the training path's kernel calls for a
    described TPU with no chip attached.
    """
    if not kernels_enabled():
        return qz.hybrid_dot(a, b, k_chunk=k_chunk)
    M, K = a.shape
    N = b.shape[-1]
    k_chunk = min(k_chunk, K)
    n_chunks = -(-K // k_chunk)
    b_limbs = qz.int8_limbs(b)
    lb_all = jnp.concatenate([lb for _, lb in b_limbs], axis=1)
    transposed = lb_all.shape[1] < _LANES <= M
    interpret = interpret_mode(backend)

    def chunk_product(la_c, lb_c):
        if transposed:          # (limbs*N, M): rows of the result on lanes
            la_c, lb_c = lb_c.T, la_c.T
        blocks = _at.block_shapes("fxp_matmul", jnp.int8,
                                  la_c.shape + lb_c.shape[1:], backend)
        return _fxp.fxp_matmul(la_c, lb_c, interpret=interpret, **blocks)

    out = None
    for wa, la in qz.int8_limbs(a):
        acc = None
        for c in range(n_chunks):
            part = chunk_product(
                la[:, c * k_chunk:(c + 1) * k_chunk],
                lb_all[c * k_chunk:(c + 1) * k_chunk]).astype(jnp.float32)
            acc = part if acc is None else acc + part
        for j, (wb, _) in enumerate(b_limbs):
            cols = (acc[j * N:(j + 1) * N] if transposed
                    else acc[:, j * N:(j + 1) * N])
            term = (wa * wb) * cols
            out = term if out is None else out + term
    return out.T if transposed else out


# ---------------------------------------------------------------------------
# kmeans_partials — fused distance -> argmin -> accumulate
# ---------------------------------------------------------------------------

def kmeans_partials(x: jax.Array, centroids: jax.Array, w: jax.Array):
    """x: (N, D) f32, centroids: (K, D), w: (N,) 0/1 row mask ->
    (sums (K, D), counts (K,), sse ()) — padding rows contribute nothing.

    >>> import jax.numpy as jnp
    >>> from repro.kernels import dispatch
    >>> x = jnp.array([[0.0, 0.0], [4.0, 4.0], [9.9, 9.9]])
    >>> c = jnp.array([[0.0, 0.0], [4.0, 4.0]])
    >>> w = jnp.array([1.0, 1.0, 0.0])       # third row is shard padding
    >>> sums, counts, sse = dispatch.kmeans_partials(x, c, w)
    >>> [int(v) for v in counts]
    [1, 1]
    >>> float(sse)
    0.0
    """
    if kernels_enabled():
        blocks = _at.block_shapes(
            "kmeans_assign", x.dtype,
            (x.shape[0], x.shape[1], centroids.shape[0]))
        return _km.kmeans_assign(x, centroids, w,
                                 interpret=interpret_mode(), **blocks)
    return _ref.kmeans_assign_ref(x, centroids, w)


def nearest_centroid(x: jax.Array, centroids: jax.Array) -> jax.Array:
    """Per-row nearest-centroid assignment — the serving-side companion
    of :func:`kmeans_partials`.  The training kernel fuses the same
    distance reduction (``|c|² − 2·x·cᵀ``; ``|x|²`` is assignment-
    invariant) straight into per-cluster sums/counts and never exposes
    the argmin, so inference shares the distance *expression* rather
    than the kernel: one MXU-shaped Gram matmul plus an argmin.

    >>> import jax.numpy as jnp
    >>> from repro.kernels import dispatch
    >>> x = jnp.array([[0.1, 0.0], [3.9, 4.2]])
    >>> c = jnp.array([[0.0, 0.0], [4.0, 4.0]])
    >>> [int(a) for a in dispatch.nearest_centroid(x, c)]
    [0, 1]
    """
    c2 = jnp.sum(centroids * centroids, axis=1)
    return jnp.argmin(c2[None, :] - 2.0 * (x @ centroids.T), axis=1)


# ---------------------------------------------------------------------------
# level_histogram — dtree split statistics
# ---------------------------------------------------------------------------

def level_histogram(node_idx: jax.Array, xbin: jax.Array, y: jax.Array,
                    w: jax.Array, *, n_nodes: int, n_bins: int,
                    n_classes: int) -> jax.Array:
    """H[node, feature, bin, class] weighted counts for one tree level."""
    if kernels_enabled():
        blocks = _at.block_shapes(
            "split_hist", jnp.float32,
            (xbin.shape[0], xbin.shape[1], n_nodes * n_bins * n_classes))
        return _sh.split_hist(node_idx, xbin, y, w, n_nodes=n_nodes,
                              n_bins=n_bins, n_classes=n_classes,
                              interpret=interpret_mode(), **blocks)
    return _ref.split_hist_ref(node_idx, xbin, y, n_nodes, n_bins,
                               n_classes, w)


# ---------------------------------------------------------------------------
# lut_apply — LUT activations (logreg sigmoid)
# ---------------------------------------------------------------------------

def lut_apply(table: lut_mod.LutTable, x: jax.Array) -> jax.Array:
    """Nearest-entry LUT evaluation of ``x`` (any shape)."""
    if kernels_enabled():
        return _lut.lut_activation(x, table.table, x_min=table.x_min,
                                   x_max=table.x_max,
                                   interpret=interpret_mode())
    return lut_mod.lut_lookup(table, x)
