"""Data pipeline with the paper's residency model (insights I3/I4).

* ``ShardedDataset`` — the classical-ML path: the training set is placed
  across the vDPU grid **once** (``PimGrid.shard_rows``) and stays
  device-resident for every iteration; per-step host traffic is zero.
* ``TokenStream`` — the LM path: an infinite deterministic synthetic
  token stream (seeded, step-addressable so restarts are exactly
  reproducible — required for fault-tolerant resume), laid out
  feature-major and sharded over the data axes.
* ``Prefetcher`` — double-buffered host->device pipeline: batch ``i+1``
  is generated/transferred while step ``i`` computes (the host-side
  mirror of insight I5's overlap).
* ``StreamingDataset`` / ``PartitionRotation`` / ``run_streaming_fit``
  — out-of-core training: the dataset lives on the host (numpy or
  ``np.memmap``) and only one resident-sized row *partition* is
  device-resident at a time, rotated between merge rounds.

DESIGN — out-of-core partition rotation
---------------------------------------

The paper's thesis is that ML training is memory-bound because it
"repeatedly accesses large training datasets" — but the engine used to
require the entire dataset device-resident per vDPU.  PIM-Opt
(arXiv 2404.07164) trains on terabyte-class Criteo; the follow-up
evaluation (arXiv 2207.07886) shows the wins hinge on keeping the
CPU<->PIM transfer off the critical path.  This module adds that
workload shape:

* **rotation = the minibatch schedule, lifted to the host.**  The
  fully-resident placement lays ``n`` rows out as ``(n_vdpus, per)``
  slots (``PimGrid.shard_rows``).  A rotation *window* ``t`` holds the
  ``part`` slots per vDPU that ``core.minibatch.batch_indices(per,
  part, seed, t)`` names — the SAME schedule definition the on-device
  sampler uses, evaluated eagerly on the host
  (``core.minibatch.host_schedule``).  Epoch-exact coverage under
  rotation is therefore the sampler's existing coverage proof: an
  epoch of ``ceil(per/part)`` windows visits every resident slot
  exactly once (the padded last window carries a zero schedule mask).
* **exactness under rotation.**  A window's partial statistics are
  scaled by ``per / n_valid`` — the sampler's unbiased-estimator
  scaling, applied as the same single tree-level multiply — so a
  streaming fit with window size ``part`` is *bit-for-bit* the
  fully-resident fit with ``batch_size=part`` and the same seed
  (``tests/test_streaming.py`` pins this), and a ``shuffle=False``
  single-partition stream is bit-for-bit the fully-resident full-batch
  fit.  Residency is an execution detail, not a semantic one.
* **rotation boundaries align with merge cadence.**  The driver
  dispatches ``steps_per_window`` local steps per window through the
  unchanged engine (``PimGrid.fit`` per window, same compiled runner
  every window — constant shapes, stable closures), requiring
  ``steps_per_window % cadence == 0`` so a window is a whole number of
  merge rounds and the scan carry layout (state[, pending], ef, mom)
  never changes shape across a swap.  EF / momentum buffers continue
  across windows through the ``merge_state`` holder exactly as they
  continue across fits.
* **quantized staging on the worker thread.**  The int8/int16
  workloads quantize each window *inside* ``stream_transform`` using
  the numpy mirror of ``quantize_fixed_scale``
  (``core.quantize.quantize_fixed_scale_np``) against the one-pass
  global scales from ``feature_absmax``/``label_absmax`` — so the
  Prefetcher worker never issues a JAX execution (which would
  serialize behind the main thread's compiled scan, see
  ``PartitionRotation.schedule``) and the staged H2D transfer ships
  the narrow integer bytes (half / quarter the float32 window).  The
  numpy and jnp paths are bit-identical (same IEEE float32
  divide / round-half-even / clip sequence; pinned by
  ``tests/test_pipeline.py``), so streamed quantized fits stay
  bit-for-bit the resident ones.
* **prefetch double-buffering.**  While window ``t`` computes, a
  ``Prefetcher`` worker gathers window ``t+1`` on the host (into a
  reused staging ring — rotation never reallocates the gather buffers)
  and stages its H2D transfer, the host-side mirror of the
  ``overlap_merge`` idiom.  Consumed windows' device buffers are
  deleted, so device residency is bounded by ``1 + depth`` partitions.
  Ingest/stall seconds are recorded per window;
  ``benchmarks/bench_streaming.py`` reports the overlap fraction.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import minibatch as mb


@dataclasses.dataclass
class ShardedDataset:
    """Memory-resident sharded dataset (see PimGrid.shard_rows)."""
    data: Any                  # pytree of (n_vdpus, rows_per_vdpu, ...)
    n_rows: int

    @classmethod
    def place(cls, grid, X, *extras):
        data, n = grid.shard_rows(X, *extras)
        return cls(data=data, n_rows=n)


class TokenStream:
    """Deterministic synthetic LM token stream.

    Markov-chain-flavored synthetic text: next token = f(prev token, rng)
    with a skewed unigram table, so models have learnable structure (loss
    drops measurably within a few hundred steps — used by the e2e train
    example).  ``batch_at(step)`` is pure in (seed, step): resume-exact.

    >>> a = TokenStream(vocab_size=64, batch=2, seq_len=8, seed=3)
    >>> b = TokenStream(vocab_size=64, batch=2, seq_len=8, seed=3)
    >>> bool((a.batch_at(7)["tokens"] == b.batch_at(7)["tokens"]).all())
    True
    """

    def __init__(self, vocab_size: int, batch: int, seq_len: int,
                 seed: int = 0, structure: float = 0.8):
        self.vocab = vocab_size
        self.batch = batch
        self.seq = seq_len
        self.seed = seed
        self.structure = structure
        rng = np.random.default_rng(seed)
        # sparse deterministic bigram successor table (8 choices per token)
        self._succ = rng.integers(0, vocab_size, size=(vocab_size, 8),
                                  dtype=np.int32)

    def batch_at(self, step: int) -> dict:
        rng = np.random.default_rng((self.seed << 20) ^ step)
        B, S = self.batch, self.seq
        toks = np.empty((B, S), np.int32)
        toks[:, 0] = rng.integers(0, self.vocab, B)
        choice = rng.integers(0, 8, (B, S))
        rand = rng.integers(0, self.vocab, (B, S), dtype=np.int32)
        use_rand = rng.random((B, S)) > self.structure
        for t in range(1, S):
            nxt = self._succ[toks[:, t - 1], choice[:, t]]
            toks[:, t] = np.where(use_rand[:, t], rand[:, t], nxt)
        return {"tokens": jnp.asarray(toks)}

    def __iter__(self) -> Iterator[dict]:
        step = 0
        while True:
            yield self.batch_at(step)
            step += 1


class Prefetcher:
    """Double-buffered background prefetch of an iterator (insight I5's
    overlap on the host side).  ``sharding`` optionally places batches;
    ``transform`` runs on the worker thread (gather / H2D staging).

    Hardened lifecycle: the worker's queue put is stop-aware (a full
    queue never deadlocks ``close``), ``close`` joins the thread, and
    ``__next__`` after ``close`` raises instead of hanging.  Per-item
    production seconds (worker-side) and consumer stall seconds land in
    ``produce_s`` / ``stall_s`` — the raw material for the streaming
    benchmark's ingest-overlap fraction.

    >>> pf = Prefetcher(iter(range(4)), depth=2)
    >>> [x for x in pf]
    [0, 1, 2, 3]
    >>> pf.close()            # idempotent after exhaustion
    >>> import pytest  # doctest: +SKIP
    """

    _SENTINEL = object()

    def __init__(self, it: Iterator, depth: int = 2,
                 sharding=None, transform: Optional[Callable] = None):
        if depth < 1:
            raise ValueError(f"Prefetcher depth must be >= 1, got {depth}")
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._closed = False
        self._done = False
        self._sharding = sharding
        self._transform = transform
        self.produce_s: list = []    # worker: seconds to produce item i
        self.stall_s: list = []      # consumer: seconds blocked for item i

        def worker():
            try:
                while True:
                    # time the FULL production: the iterator pull (the
                    # host gather lives inside the generator) plus the
                    # transform/H2D — this is the ingest the overlap
                    # fraction is measured against
                    t0 = time.perf_counter()
                    try:
                        item = next(it)
                    except StopIteration:
                        break
                    if self._stop.is_set():
                        return
                    if self._transform:
                        item = self._transform(item)
                    if self._sharding is not None:
                        item = jax.tree.map(
                            lambda x: jax.device_put(x, self._sharding),
                            item)
                    self.produce_s.append(time.perf_counter() - t0)
                    if not self._put(item):
                        return
            finally:
                self._put(self._SENTINEL)

        self._thread = threading.Thread(target=worker, daemon=True)
        self._thread.start()

    def _put(self, item) -> bool:
        """Stop-aware put: never blocks forever on a full queue."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return True
            except queue.Full:
                continue
        return False

    def __iter__(self):
        return self

    def __next__(self):
        if self._closed:
            raise RuntimeError(
                "Prefetcher is closed — __next__ would never produce "
                "an item")
        if self._done:
            raise StopIteration
        t0 = time.perf_counter()
        item = self._q.get()
        if item is self._SENTINEL or item is None:
            # None kept for backward compatibility with iterators that
            # used it as an explicit end marker
            self._done = True
            raise StopIteration
        self.stall_s.append(time.perf_counter() - t0)
        return item

    def close(self):
        """Stop the worker, join it, and invalidate the iterator.
        Idempotent; safe to call with the queue full (the worker's put
        is stop-aware) or with a consumer blocked in ``__next__`` (the
        drained queue is re-primed with the sentinel)."""
        if self._closed:
            return
        self._closed = True
        self._stop.set()
        # unblock a worker stuck in put()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=10.0)
        # wake any consumer that was already blocked in get()
        try:
            self._q.put_nowait(self._SENTINEL)
        except queue.Full:
            pass


# ---------------------------------------------------------------------------
# Out-of-core streaming ingestion
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class StreamingDataset:
    """An out-of-core training source: host-side row arrays (numpy or
    ``np.memmap``) partitioned into resident-sized row partitions that
    rotate through device memory during a fit.

    ``partition_rows`` is the global resident-row budget (rows resident
    across the whole grid at once).  ``steps_per_window`` local steps
    run per resident window (default: one merge round — the plan's
    cadence).  ``shuffle=True`` draws the per-epoch partition order
    from the sampler's ``fold_in(seed, epoch)`` permutation;
    ``shuffle=False`` tiles sequentially (the bit-exact whole-dataset
    layout).

    >>> import numpy as np
    >>> sd = StreamingDataset(np.ones((100, 4), np.float32),
    ...                       np.zeros(100, np.float32),
    ...                       partition_rows=32)
    >>> sd.n_rows, sd.n_features
    (100, 4)
    """

    is_streaming_source = True

    X: Any
    y: Any = None
    partition_rows: int = 0
    prefetch_depth: int = 2
    steps_per_window: Optional[int] = None
    seed: int = 0
    shuffle: bool = True

    def __post_init__(self):
        self.X = np.asarray(self.X)
        if self.y is not None:
            self.y = np.asarray(self.y)
            if len(self.y) != len(self.X):
                raise ValueError(
                    f"X has {len(self.X)} rows but y has {len(self.y)}")
        if self.partition_rows < 1:
            raise ValueError(
                f"partition_rows must be >= 1, got {self.partition_rows}")
        if self.prefetch_depth < 0:
            raise ValueError(
                f"prefetch_depth must be >= 0, got {self.prefetch_depth}")
        if self.steps_per_window is not None and self.steps_per_window < 1:
            raise ValueError(
                f"steps_per_window must be >= 1, got "
                f"{self.steps_per_window}")

    @property
    def n_rows(self) -> int:
        return int(self.X.shape[0])

    @property
    def n_features(self) -> int:
        return int(self.X.shape[1])

    def rows(self, idx) -> np.ndarray:
        """Random access into the host rows (kmeans' centroid init)."""
        return np.take(self.X, np.asarray(idx), axis=0)

    def feature_absmax(self, block_rows: int = 1 << 18) -> np.ndarray:
        """Per-feature ``max |x|`` in one blocked host pass — the
        global statistic the quantized streaming paths derive their
        fixed scales from (matches ``quantize_symmetric(axis=0)``'s
        reduction over the full dataset)."""
        amax = np.zeros((1, self.n_features), np.float32)
        for lo in range(0, self.n_rows, block_rows):
            blk = np.abs(np.asarray(self.X[lo:lo + block_rows],
                                    np.float32))
            np.maximum(amax, blk.max(axis=0, keepdims=True), out=amax)
        return amax

    def label_absmax(self, block_rows: int = 1 << 18) -> np.float32:
        amax = np.float32(0.0)
        for lo in range(0, self.n_rows, block_rows):
            blk = np.abs(np.asarray(self.y[lo:lo + block_rows],
                                    np.float32))
            amax = np.maximum(amax, blk.max() if blk.size else 0.0)
        return np.float32(amax)

    def bind(self, grid, transform: Optional[Callable] = None
             ) -> "PartitionRotation":
        """Bind the rotation to a grid for raw ``grid.fit`` use (the
        workload layer binds through ``Workload.bind_stream``).
        ``transform(X_rows, y_rows) -> (X', extra0, ...)`` maps raw
        host rows to the resident representation (labels, quantization)
        — identity by default."""
        return PartitionRotation(self, grid, transform=transform)


class _StagingRing:
    """A reused ring of host gather buffers: rotation never reallocates
    the gather staging, whatever the window count (the host-side
    analogue of the engine's donated carry buffers)."""

    def __init__(self, size: int):
        self._size = max(2, size)
        self._bufs: list = [None] * self._size
        self._i = 0

    def take(self, src: np.ndarray, flat_idx: np.ndarray) -> np.ndarray:
        shape = (len(flat_idx),) + src.shape[1:]
        buf = self._bufs[self._i]
        if buf is None or buf.shape != shape or buf.dtype != src.dtype:
            buf = np.empty(shape, src.dtype)
            self._bufs[self._i] = buf
        np.take(src, flat_idx, axis=0, out=buf, mode="clip")
        self._i = (self._i + 1) % self._size
        return buf


class PartitionRotation:
    """A :class:`StreamingDataset` bound to a grid: produces the
    per-window device dicts the engine consumes, in the epoch-exact
    rotation order (see the module DESIGN).

    The window dict mirrors ``PimGrid.shard_rows``'s convention —
    ``{"X", "w", "y0", ...}`` shaped ``(n_vdpus, part, ...)`` — plus a
    per-vDPU ``"scale"`` leaf carrying the unbiased-estimator scaling
    ``per / n_valid`` that the streaming driver applies to each
    window's partial statistics (the sampler's scaling, hoisted).
    """

    is_streaming_rotation = True

    def __init__(self, stream: StreamingDataset, grid,
                 transform: Optional[Callable] = None):
        self.stream = stream
        self.grid = grid
        self._transform = transform
        n, nv = stream.n_rows, grid.n_vdpus
        self.per = -(-n // nv)                      # resident slots/vDPU
        self.part = max(1, min(self.per,
                               -(-stream.partition_rows // nv)))
        self.windows_per_epoch = mb.epoch_steps(self.per, self.part)
        # single-window rotation: every window is the whole resident
        # layout, the schedule mask is all-ones and the scale exactly
        # 1.0 — so the driver skips the scale wrapper and (with
        # shuffle=False) runs the IDENTICAL compiled graph the
        # fully-resident fit runs.  Bit-for-bit by construction, not by
        # hoping XLA fuses a ×1.0 the same way.
        self.exact_full = self.part == self.per
        self._ring = _StagingRing(stream.prefetch_depth + 2)
        self._sched_cache: dict = {}
        self.last_run_stats: Optional[dict] = None

    # -- schedule ------------------------------------------------------

    def steps_per_window(self, cadence: int) -> int:
        """Local steps per resident window: the stream's explicit
        setting, or one merge round.  Rotation boundaries must align
        with merge cadence (the carry layout is shaped per-round)."""
        spw = self.stream.steps_per_window
        if spw is None:
            spw = cadence
        if spw % cadence:
            raise ValueError(
                f"steps_per_window={spw} must be a multiple of the "
                f"merge cadence {cadence}: a rotation boundary inside "
                f"a merge round would swap data under vDPU-divergent "
                f"states")
        return spw

    def schedule(self, t: int):
        """``(idx, mask)`` for window ``t`` — ``mb.host_schedule``
        memoized.  The schedule is a JAX ``fold_in``/``permutation``
        computation (what makes it bit-identical to the on-device
        sampler), and JAX executions from the prefetch worker would
        serialize behind the main thread's compiled scan — so the
        driver prewarms schedules on the main thread and the worker
        only ever does the numpy gather + H2D."""
        got = self._sched_cache.get(t)
        if got is None:
            got = mb.host_schedule(self.per, self.part,
                                   self.stream.seed, t,
                                   shuffle=self.stream.shuffle)
            self._sched_cache[t] = got
            while len(self._sched_cache) > 4096:
                self._sched_cache.pop(next(iter(self._sched_cache)))
        return got

    def prewarm_schedules(self, ts) -> None:
        """Materialize window schedules ahead of a fit (main thread)."""
        for t in ts:
            self.schedule(t)

    def tag(self) -> str:
        """Identity of the rotation schedule — checkpointed by the
        Trainer so a resumed run refuses a drifted partition layout."""
        s = self.stream
        return (f"rotation(n={s.n_rows}, n_vdpus={self.grid.n_vdpus}, "
                f"part={self.part}, spw={s.steps_per_window}, "
                f"seed={s.seed}, shuffle={s.shuffle})")

    # -- window materialization ---------------------------------------

    def window_host(self, t: int) -> dict:
        """Host-side arrays for rotation window ``t`` — pure in
        ``(seed, t)``, so replaying a window replays its rows (what
        makes SIGKILL-resume exact)."""
        s, nv, per, part = self.stream, self.grid.n_vdpus, self.per, \
            self.part
        idx, mask = self.schedule(t)
        n = s.n_rows
        # slot (v, i) -> global row v*per + idx[i]; rows past n are the
        # shard padding (zero rows, w=0) — same layout as shard_rows
        rows = (np.arange(nv, dtype=np.int64)[:, None] * per
                + idx[None, :])
        real = (rows < n).astype(np.float32)
        flat = rows.ravel()
        Xb = self._ring.take(s.X, flat)
        yb = None if s.y is None else np.take(s.y, np.clip(flat, 0,
                                                           n - 1), axis=0)
        if self._transform is not None:
            out = self._transform(Xb, yb)
        else:
            out = (Xb,) if yb is None else (Xb, yb)
        Xt, extras = out[0], out[1:]
        # padding slots must hold zeros exactly like shard_rows' pad
        w = real * mask[None, :]
        valid = np.float32(mask.sum(dtype=np.float32))
        scale = np.float32(per) / np.maximum(valid, np.float32(1.0))
        d = {"X": np.asarray(Xt).reshape((nv, part)
                                         + np.shape(Xt)[1:]),
             "w": w}
        for i, e in enumerate(extras):
            d[f"y{i}"] = np.asarray(e).reshape((nv, part)
                                               + np.shape(e)[1:])
        # zero out pad rows so padding never contaminates statistics
        # that read values without the w mask (none do today, but
        # shard_rows guarantees it, so the rotation does too)
        wz = w.astype(bool)
        d["X"] = np.where(wz[(...,) + (None,) * (d["X"].ndim - 2)],
                          d["X"], np.zeros((), d["X"].dtype))
        if not self.exact_full:
            d["scale"] = np.full((nv,), scale, np.float32)
        return d

    def place(self, host_dict: dict) -> dict:
        """H2D: place a window on the grid's data sharding."""
        sharding = self.grid.data_sharding()
        if sharding is None:
            return jax.tree.map(jnp.asarray, host_dict)
        return jax.tree.map(
            lambda x: jax.device_put(jnp.asarray(x), sharding),
            host_dict)

    def window_data(self, t: int) -> dict:
        """Materialized device window (synchronous fetch path)."""
        return self.place(self.window_host(t))

    def windows(self, start: int = 0) -> Iterator[dict]:
        """Infinite host-window iterator from window ``start``."""
        t = start
        while True:
            yield self.window_host(t)
            t += 1

    def prefetcher(self, start: int = 0,
                   depth: Optional[int] = None) -> Prefetcher:
        depth = self.stream.prefetch_depth if depth is None else depth
        return Prefetcher(self.windows(start), depth=max(1, depth),
                          transform=self.place)


def _release_window(d: Optional[dict]) -> None:
    """Free a consumed window's device buffers so residency stays
    bounded at (1 + depth) partitions."""
    if d is None:
        return
    for leaf in jax.tree.leaves(d):
        if isinstance(leaf, jax.Array):
            try:
                leaf.delete()
            except RuntimeError:
                pass


def make_scaled_local(local_fn: Callable) -> Callable:
    """Wrap an engine ``local_fn`` for streaming windows: strip the
    rotation's ``"scale"`` leaf from the slice and apply it to the
    partial-statistics tree — the exact multiply the on-device sampler
    performs, hoisted to the window level.

    ``merge_plan.fn_signature`` keys the wrapper by ``local_fn``'s own
    signature, so the wrappers of equal workload configurations share
    the grid's compiled runners across windows AND across fits —
    rebinding a streaming program never retraces.  A trailing array
    operand (``PimGrid.fit``'s ``consts``) passes through to
    ``local_fn``."""
    def streaming_local_fn(state, sl, *consts, _lf=local_fn):
        scale = sl["scale"]
        rows = {k: v for k, v in sl.items() if k != "scale"}
        part = _lf(state, rows, *consts)
        return jax.tree.map(lambda x: x * scale, part)

    return streaming_local_fn


def run_streaming_fit(grid, rotation: PartitionRotation, *, init_state,
                      local_fn, update_fn, steps: int, plan,
                      merge_state: Optional[dict] = None,
                      callback: Optional[Callable] = None,
                      scan_chunk: int = 32, engine: str = "scan",
                      consts=None):
    """The out-of-core training driver: rotate resident partitions
    through ``PimGrid.fit`` while the prefetcher double-buffers the
    next window's gather + H2D behind the current window's compute.

    Dispatched by ``PimGrid.fit`` when ``data`` is a
    :class:`PartitionRotation`; the per-window fits reuse the whole
    engine unchanged (scan/python, cadence, overlap, compression,
    outer optimizers — EF/momentum continue across windows through
    ``merge_state``).  Returns ``(state, history)`` with one history
    entry per local step, and leaves ingest/stall/overlap statistics in
    ``rotation.last_run_stats`` (mirrored into
    ``merge_state["streaming_trace"]`` when a holder rides along).
    """
    if plan.adaptive or plan.auto:
        raise ValueError(
            "streaming ingestion cannot drive controller plans "
            "(AdaptiveCadence / merge_plan=\"auto\"): the controller "
            "re-probes per fit, and a per-window probe would measure "
            "rotation noise, not the plan — pick an explicit MergePlan")
    spw = rotation.steps_per_window(plan.cadence)
    scaled_lf = (local_fn if rotation.exact_full
                 else make_scaled_local(local_fn))
    depth = rotation.stream.prefetch_depth

    state = init_state
    history: list = []
    done = 0
    window = 0
    prev_data: Optional[dict] = None
    produce_s: list = []
    stall_s: list = []
    # schedules are JAX computations — materialize them on the main
    # thread so the prefetch worker never queues behind the scan
    rotation.prewarm_schedules(range(-(-steps // spw)))
    pf = rotation.prefetcher(0) if depth >= 1 else None
    try:
        while done < steps:
            t0 = time.perf_counter()
            if pf is not None:
                data = next(pf)
                stall = time.perf_counter() - t0
            else:
                data = rotation.window_data(window)
                stall = time.perf_counter() - t0
                produce_s.append(stall)          # fully exposed ingest
            stall_s.append(stall)
            k = min(spw, steps - done)
            cb = None
            if callback is not None:
                def cb(step, st, m, _off=done, _cb=callback):
                    return _cb(_off + step, st, m)
            state, h = grid.fit(
                init_state=state, local_fn=scaled_lf,
                update_fn=update_fn, data=data, steps=k,
                merge_plan=plan, merge_state=merge_state,
                engine=engine, scan_chunk=scan_chunk, callback=cb,
                consts=consts)
            jax.block_until_ready(state)
            history.extend(h)
            done += k
            window += 1
            _release_window(prev_data)
            prev_data = data
    finally:
        if pf is not None:
            produce_s = list(pf.produce_s)
            pf.close()
        _release_window(prev_data)

    # steady-state overlap: the pipeline-fill windows (the first
    # min(depth, windows-1)) pay their ingest by construction
    skip = min(max(depth, 1), max(len(stall_s) - 1, 0))
    ingest_steady = float(sum(produce_s[skip:len(stall_s)]))
    stall_steady = float(sum(stall_s[skip:]))
    overlap = (1.0 - min(stall_steady / ingest_steady, 1.0)
               if ingest_steady > 0 else 1.0)
    stats = {
        "windows": len(stall_s),
        "windows_per_epoch": rotation.windows_per_epoch,
        "steps_per_window": spw,
        "prefetch_depth": depth,
        "ingest_s": float(sum(produce_s[:len(stall_s)])),
        "stall_s": float(sum(stall_s)),
        "ingest_s_steady": ingest_steady,
        "stall_s_steady": stall_steady,
        "ingest_overlap_fraction": overlap,
    }
    rotation.last_run_stats = stats
    if merge_state is not None:
        merge_state["streaming_trace"] = stats
    return state, history


class RotationFeed:
    """A deterministic ``batch_fn(step)`` over a rotation for the
    fault-tolerant Trainer: window ``step // steps_per_window``,
    prefetched sequentially, rebuilt on any non-sequential request
    (restore/replay rollback re-gathers the rolled-back window)."""

    def __init__(self, rotation: PartitionRotation,
                 steps_per_window: int):
        if steps_per_window < 1:
            raise ValueError(
                f"steps_per_window must be >= 1, got {steps_per_window}")
        self.rotation = rotation
        self.spw = steps_per_window
        self._pf: Optional[Prefetcher] = None
        self._cur_w = -1
        self._cur: Optional[dict] = None

    def __call__(self, step: int) -> dict:
        w = step // self.spw
        if w == self._cur_w:
            return self._cur
        depth = self.rotation.stream.prefetch_depth
        # keep the schedule horizon warm so the prefetch worker's JAX
        # schedule draw never serializes behind the trainer's compute
        self.rotation.prewarm_schedules(range(w, w + depth + 2))
        if self._pf is None or w != self._cur_w + 1:
            if self._pf is not None:
                self._pf.close()
            self._pf = (self.rotation.prefetcher(w)
                        if depth >= 1 else None)
        prev = self._cur
        if self._pf is not None:
            self._cur = next(self._pf)
        else:
            self._cur = self.rotation.window_data(w)
        self._cur_w = w
        _release_window(prev)
        return self._cur

    def close(self):
        if self._pf is not None:
            self._pf.close()
            self._pf = None
