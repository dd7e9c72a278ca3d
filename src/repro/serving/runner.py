"""Compiled batch-inference runners: ``Workload.predict`` behind a
bucket ladder of ahead-of-time-compiled executables.

Request traffic arrives at arbitrary batch sizes; XLA specializes on
shapes.  Served naively, every distinct request size would trigger a
fresh compile — the serving analogue of the retrace bug the training
engine's signature-keyed compile cache exists to prevent.  The
:class:`PredictRunner` closes the shape set instead:

* requests pad with zero rows up to a small **bucket ladder**
  (default 8 / 32 / 128 / 512 rows) and the result is sliced back to
  the true length — ``Workload.predict`` is pad-invariant by contract
  (zero rows never move a per-feature quantization absmax, and every
  forward reduction is row-local);
* batches larger than the top bucket split into top-bucket chunks plus
  one bucketed remainder, so the compiled set stays closed for *any*
  request size;
* each (workload, bucket, n_features, precision) compiles exactly once,
  through the grid's existing fit cache (``merge_plan.cache_get`` /
  ``cache_put`` keyed by ``fn_signature`` — the workload instance keys
  by value, so two runners serving equal estimator configurations share
  executables, including across registry hot-swaps: the model state is
  an *argument* of the compiled function, never a baked-in constant);
* the padded input buffer is not donated: a prediction never has the
  request's ``(bucket, features)`` shape, so XLA could not reuse it;
* :meth:`run_stream` double-buffers host→device staging behind compute,
  the same idiom ``overlap_merge`` / the streaming ``Prefetcher`` use:
  dispatch for batch *i* returns before its result materializes, so
  batch *i+1*'s H2D transfer is issued while *i* is still computing.

Counters (``bucket_hits`` / ``compile_misses`` /
``steady_compile_misses``) make the warm-cache claim testable: after
:meth:`warmup` (or one pass over the ladder), steady-state traffic must
report zero further compiles.
"""

from __future__ import annotations

import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import merge_plan as mp

DEFAULT_BUCKETS = (8, 32, 128, 512)


class PredictRunner:
    """Bucketed, AOT-compiled ``workload.predict(state, X)``.

    ``grid`` is optional: when given, compiled executables live in the
    grid's fit cache (shared across runners and hot-swapped versions);
    without one the runner keeps a private cache.

    >>> import numpy as np
    >>> from repro.core.mlalgos.linreg import LinReg
    >>> r = PredictRunner(LinReg(), jnp.ones(3), buckets=(4, 8))
    >>> r.warmup(3)                 # compile the ladder, arm counters
    >>> np.asarray(r.predict(np.eye(3, dtype=np.float32))).tolist()
    [1.0, 1.0, 1.0]
    >>> r.bucket_for(6), r.bucket_for(100)      # oversize -> chunked
    (8, None)
    >>> r.counters()["steady_compile_misses"]
    0
    """

    def __init__(self, workload, state, *,
                 buckets: Sequence[int] = DEFAULT_BUCKETS,
                 grid=None):
        if not getattr(workload, "predict_device", True):
            raise ValueError(
                f"workload {workload.name!r} declares "
                f"predict_device=False (host-only forward pass) — the "
                f"compiled PredictRunner cannot trace it; call "
                f"workload.predict directly instead")
        if not buckets or any(b <= 0 for b in buckets):
            raise ValueError(f"bucket ladder must be positive: {buckets}")
        self.workload = workload
        self.state = state
        self.buckets = tuple(sorted(set(int(b) for b in buckets)))
        self.grid = grid
        self._private_cache: dict = {}
        self._lock = threading.Lock()

        # the traced function: the workload rides in a default arg so
        # fn_signature keys it by value (equal estimator configs share
        # executables); state is an argument, so version swaps reuse
        # compiled code as long as the state shapes match
        def fwd(state, X, _w=workload):
            return _w.predict(state, X)

        self._fwd = fwd

        self.bucket_hits = 0
        self.compile_misses = 0
        self.steady_compile_misses = 0
        self._warm = False

    # -- compile cache -------------------------------------------------

    def _state_aval(self):
        return tuple((tuple(l.shape), str(jnp.asarray(l).dtype))
                     for l in jax.tree.leaves(self.state))

    def _compiled(self, bucket: int, d: int):
        """The executable for one (bucket, features) cell — compiled at
        most once per (workload, bucket, d, state shapes, backend)."""
        key = ("serving", mp.fn_signature(self._fwd), bucket, d,
               self._state_aval())
        with self._lock:
            if self.grid is not None:
                hit = mp.cache_get(self.grid, key)
            else:
                hit = self._private_cache.get(key)
            if hit is not None:
                return hit
            self.compile_misses += 1
            if self._warm:
                self.steady_compile_misses += 1
            exe = jax.jit(self._fwd).lower(
                self.state,
                jax.ShapeDtypeStruct((bucket, d), jnp.float32)).compile()
            if self.grid is not None:
                mp.cache_put(self.grid, key, exe, self._fwd, self._fwd)
            else:
                self._private_cache[key] = exe
            return exe

    def bucket_for(self, n: int) -> Optional[int]:
        """Smallest ladder bucket holding ``n`` rows (None: oversize,
        the caller chunks by the top bucket)."""
        for b in self.buckets:
            if n <= b:
                return b
        return None

    def mark_warm(self):
        """Declare warmup over: any further compile is a steady-state
        miss (the counter the zero-miss acceptance test reads)."""
        self._warm = True

    def warmup(self, d: int):
        """Compile the whole ladder for ``d`` features, then arm the
        steady-state miss counter."""
        for b in self.buckets:
            self._compiled(b, d)
        self.mark_warm()

    # -- the serve path ------------------------------------------------

    def _pad(self, Xn: np.ndarray, bucket: int) -> np.ndarray:
        if Xn.shape[0] == bucket:
            return Xn
        out = np.zeros((bucket, Xn.shape[1]), Xn.dtype)
        out[: Xn.shape[0]] = Xn
        return out

    def _run_bucket(self, Xn: np.ndarray, bucket: int):
        exe = self._compiled(bucket, Xn.shape[1])
        self.bucket_hits += 1
        out = exe(self.state, self._pad(Xn, bucket))
        return out[: Xn.shape[0]]

    def predict(self, X):
        """Serve one request batch of any size: pad to the bucket
        ladder (oversize splits into top-bucket chunks + a bucketed
        remainder), run the compiled forward, slice the padding off."""
        Xn = np.asarray(X, np.float32)
        if Xn.ndim != 2:
            raise ValueError(
                f"predict expects (rows, features), got {Xn.shape}")
        n = Xn.shape[0]
        if n == 0:
            raise ValueError("empty request batch")
        b = self.bucket_for(n)
        if b is not None:
            return self._run_bucket(Xn, b)
        top = self.buckets[-1]
        parts = [self._run_bucket(Xn[i:i + top], top)
                 for i in range(0, n - n % top, top)]
        rem = n % top
        if rem:
            parts.append(self._run_bucket(Xn[n - rem:],
                                          self.bucket_for(rem)))
        return jnp.concatenate(parts, axis=0)

    def run_stream(self, batches):
        """Serve an iterable of equal-width batches with host↔device
        double-buffering: compute for batch *i* is dispatched (async)
        before its result is awaited, so batch *i+1*'s padding + H2D
        staging overlaps *i*'s device time — the ``overlap_merge`` /
        ``Prefetcher`` idiom applied to the serve path.  Yields one
        un-padded prediction array per input batch, in order."""
        pending = None          # (true_rows, in-flight device result)
        for X in batches:
            Xn = np.asarray(X, np.float32)
            b = self.bucket_for(Xn.shape[0])
            if b is None:
                raise ValueError(
                    f"run_stream batches must fit the ladder "
                    f"(≤ {self.buckets[-1]} rows), got {Xn.shape[0]}")
            exe = self._compiled(b, Xn.shape[1])
            staged = jax.device_put(jnp.asarray(self._pad(Xn, b)))
            if pending is not None:
                yield pending[1][: pending[0]]
            self.bucket_hits += 1
            pending = (Xn.shape[0], exe(self.state, staged))
        if pending is not None:
            yield pending[1][: pending[0]]

    def counters(self) -> dict:
        return {"bucket_hits": self.bucket_hits,
                "compile_misses": self.compile_misses,
                "steady_compile_misses": self.steady_compile_misses}
