"""K-means clustering (Lloyd's algorithm) on the PIM grid.

Paper workload #4.  Per iteration each DPU streams its resident points,
assigns each to the nearest centroid, and accumulates per-cluster partial
sums and counts; the host merges partials and recomputes centroids.

TPU adaptation of the inner loop (DESIGN.md §2): instead of the DPU's
scalar accumulation we compute assignments with a distance matrix and
accumulate with a one-hot matmul — both MXU-shaped.  The fused
distance->argmin->accumulate hotspot runs on the `kernels/kmeans_assign`
Pallas kernel via `kernels.dispatch.kmeans_partials` (interpret-mode jnp
emulation off-TPU; `dispatch.use_kernels(False)` flips to the pure-jnp
reference).

Fixed-point path (insight I1): points stored int16/int8 in the row
format of :mod:`~repro.core.mlalgos.fixed_point`, dequantized on the
stream into the float distance kernel.

Implemented as a :class:`~repro.core.mlalgos.api.Workload` plugin;
``train_kmeans`` is a thin wrapper.  ``batch_size=b`` gives minibatch
k-means: each Lloyd iteration assigns a sampled subset per vDPU, with
the partial sums/counts scaled to partition magnitude (the update stays
the same safe-mean).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.mlalgos import api
from repro.core.mlalgos.fixed_point import Precision, row_format
from repro.core.pim import PimGrid
from repro.kernels import dispatch


@dataclasses.dataclass
class KMeansResult:
    centroids: jax.Array      # (k, d)
    history: list             # per-iter {"sse": ..., "moved": ...}
    precision: str


@dataclasses.dataclass(frozen=True)
class KMeans(api.Workload):
    """Lloyd's algorithm; state = the (k, d) centroid matrix."""

    k: int = 8
    precision: Precision = "fp32"
    seed: int = 0

    name = "kmeans"

    def prepare(self, grid: PimGrid, X, y=None):
        n_rows = X.shape[0]
        key = jax.random.PRNGKey(self.seed)
        init_idx = jax.random.choice(key, n_rows, (self.k,),
                                     replace=False)
        c0 = jnp.asarray(X)[init_idx]
        Xv, scales = row_format(self.precision).place(X)
        data, n = grid.shard_rows(Xv)
        return data, n, {"n": n, "_c0": c0, **scales}

    def stream_consts(self, stream):
        n = stream.n_rows
        key = jax.random.PRNGKey(self.seed)
        init_idx = jax.random.choice(key, n, (self.k,), replace=False)
        # same draw as prepare; the stream's random row access stands
        # in for fancy-indexing the resident array
        c0 = jnp.asarray(stream.rows(init_idx))
        return {"n": n, "_c0": c0,
                **row_format(self.precision).stream_scales(
                    stream.feature_absmax)}

    def stream_transform(self, consts, X_rows, y_rows):
        return (row_format(self.precision).stage(consts, X_rows),)

    def init_state(self, consts):
        return consts["_c0"]

    def local_step(self, consts, centroids, sl):
        xf = row_format(self.precision).dequantize(consts, sl["X"])
        sums, counts, sse = dispatch.kmeans_partials(
            xf, centroids, sl["w"])
        return {"sums": sums, "counts": counts, "sse": sse}

    def update(self, consts, centroids, merged):
        counts = merged["counts"]
        safe = jnp.maximum(counts, 1.0)[:, None]
        new_c = merged["sums"] / safe
        # empty clusters keep their previous centroid (paper's policy)
        new_c = jnp.where(counts[:, None] > 0, new_c, centroids)
        moved = jnp.max(jnp.abs(new_c - centroids))
        return new_c, {"sse": merged["sse"], "moved": moved}

    def eval(self, state, X, y=None) -> dict:
        assign = kmeans_assign_points(state, X)
        d2 = jnp.sum((jnp.asarray(X) - state[assign]) ** 2)
        return {"sse": float(d2)}

    def predict(self, state, X):
        """Serving nearest-centroid assignment — bit-exact with the
        :func:`kmeans_assign_points` ``eval`` uses (both delegate to
        ``dispatch.nearest_centroid``).  Quantized configurations mirror
        ``local_step``'s dequantize-on-stream: the request rows pass
        through the row format before the distance reduction."""
        fmt = row_format(self.precision)
        Xv, scales = fmt.request(jnp.asarray(X))
        return dispatch.nearest_centroid(fmt.dequantize(scales, Xv), state)


def train_kmeans(grid: PimGrid, X: jax.Array, k: int, *,
                 iters: int = 20, precision: Precision = "fp32",
                 seed: int = 0, engine: str = "scan",
                 merge_every: int = 1, overlap_merge: bool = False,
                 merge_compression=None,
                 merge_state: dict | None = None,
                 merge_plan=None, batch_size: int | None = None,
                 sample_seed: int = 0) -> KMeansResult:
    """``merge_every=m`` runs m vDPU-local Lloyd iterations between
    centroid merges (each vDPU updates its own centroid copy from its
    resident points; the merge averages the copies).  ``m=1`` is the
    paper's exact merge-per-iteration algorithm, bit-exact with the
    PR 1 engine.  ``overlap_merge``/``merge_compression`` select the
    overlapped / compressed merge pipeline; the int8 wire quantizes the
    float cluster sums/counts with error feedback (counts survive
    because EF carries the rounding residual into the next merge).
    ``batch_size=b`` runs minibatch k-means on b sampled resident rows
    per vDPU per iteration (``None`` = full partitions, exact)."""
    res = api.fit(KMeans(k=k, precision=precision, seed=seed),
                  grid, X, steps=iters, engine=engine,
                  merge_every=merge_every, overlap_merge=overlap_merge,
                  merge_compression=merge_compression,
                  merge_state=merge_state, merge_plan=merge_plan,
                  batch_size=batch_size, sample_seed=sample_seed)
    return KMeansResult(centroids=res.state, history=res.history,
                        precision=precision)


def kmeans_assign_points(centroids: jax.Array, X: jax.Array) -> jax.Array:
    """Nearest-centroid assignment (``dispatch.nearest_centroid`` with
    the historical argument order kept for eval/test call sites)."""
    return dispatch.nearest_centroid(jnp.asarray(X), centroids)
