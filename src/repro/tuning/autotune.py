"""Block-shape selection for the Pallas kernels — measured or heuristic,
with an on-disk cache.  (Re-homed from ``repro.kernels.autotune``: block
shapes are one axis of the unified tuning layer, next to the merge-plan
controller and the roofline cost model.)

The kernels (`fxp_matmul`, `kmeans_assign`, `split_hist`) take their
block shapes as parameters but historically ran with fixed constants
chosen for one TPU generation.  The right shapes depend on four things —
which kernel, the operand dtype (int8 tiles are (32, 128), f32 (8, 128)),
the problem shape, and the backend (Mosaic wants MXU-aligned VMEM-sized
tiles; the CPU/GPU ``interpret=True`` fallback executes the kernel body
once *per grid step* in Python, so fewer/larger blocks win as long as
they fit in memory).  This module owns that decision:

* ``block_shapes(kernel, dtype, shape)`` — the dispatch-time entry
  point.  Returns the measured table entry when one exists for the
  ``(kernel, dtype, shape-bucket, backend)`` key, else the per-backend
  heuristic.  Pure Python over static shapes, so it is free at trace
  time.
* ``autotune(kernel, shape, dtype)`` — the measured path: times each
  candidate block shape on representative inputs with the real kernel
  and persists the winner to the on-disk cache
  (``$REPRO_AUTOTUNE_CACHE`` or ``.autotune/blocks.json`` in the
  checkout), so the cost is paid once per checkout, not per process.
  It raises when no candidate lowers.
  ``measure_candidates`` exposes the raw timings as the same
  ``Measurement`` records the plan controller consumes.

Candidate sets are data-driven: ``CANDIDATE_TABLE`` declares them per
``(kernel, backend)`` with symbolic entries (a dim name takes that dim's
full extent, ``["heur", f]`` scales the heuristic) and
``register_candidates`` extends the table at runtime — a new backend or
kernel adds rows, not code.

Cache keying: shapes are bucketed to the next power of two per
dimension — a (300, 130) matmul and a (512, 256) one share an entry —
and the backend rides in the key so a cache written on CPU never
steers a TPU run.  Writes go to a per-writer temp file followed by an
atomic ``os.replace``, so concurrent writers can interleave freely: the
last writer wins an entry, but the JSON on disk is always complete.
"""

from __future__ import annotations

import json
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.tuning.measurement import Measurement

# interpret-mode blocks are capped by element budgets rather than VMEM:
# the whole block materializes as a jnp array per grid step.
_INTERPRET_ELEMS = 1 << 22       # ~16 MB of f32 per operand block
_ONEHOT_ELEMS = 1 << 24          # split_hist's F (n*b*c, bn) one-hots
_VMEM_ELEMS = 1 << 20            # ~4 MB of f32 — conservative VMEM share
# split_hist's budget: half of v5e's 16 MiB default scoped VMEM in f32
# elements, the rest left to Mosaic's own copies and the input buffers
_SPLIT_HIST_VMEM_ELEMS = 1 << 21

_CACHE_ENV = "REPRO_AUTOTUNE_CACHE"
# inside the checkout (and ignored by git), so a run reads no table it did
# not write: on a fresh checkout the heuristic applies
_DEFAULT_CACHE = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..",
    ".autotune", "blocks.json")

_lock = threading.Lock()
_cache: Optional[dict] = None
_cache_path_loaded: Optional[str] = None

# dim-name -> shape axis, per kernel: the vocabulary CANDIDATE_TABLE
# entries may use symbolically
KERNEL_DIMS: Dict[str, Dict[str, int]] = {
    "fxp_matmul": {"block_m": 0, "block_k": 1, "block_n": 2},
    "kmeans_assign": {"block_n": 0},
    "split_hist": {"block_n": 0},
}
_DIM_NAMES: Dict[str, Dict[str, int]] = {
    "fxp_matmul": {"M": 0, "K": 1, "N": 2},
    "kmeans_assign": {"N": 0, "D": 1, "K": 2},
    "split_hist": {"N": 0, "F": 1},
}


def cache_path() -> str:
    return os.path.normpath(os.path.expanduser(
        os.environ.get(_CACHE_ENV, _DEFAULT_CACHE)))


def _load_cache() -> dict:
    global _cache, _cache_path_loaded
    path = cache_path()
    with _lock:
        if _cache is not None and _cache_path_loaded == path:
            return _cache
        entries: dict = {}
        try:
            with open(path) as f:
                data = json.load(f)
            if isinstance(data, dict):
                entries = data.get("entries", {})
        except (OSError, ValueError):
            pass
        _cache = entries
        _cache_path_loaded = path
        return _cache


def _store(key: str, blocks: Dict[str, int], us: float):
    global _cache, _cache_path_loaded
    # merge into what's on disk, not just this process's view — a fresh
    # process whose first act is autotune() must not wipe entries other
    # runs persisted (loaded outside the non-reentrant lock)
    entries = dict(_load_cache())
    path = cache_path()
    with _lock:
        entries.update(_cache or {})
        entries[key] = {"blocks": blocks, "us": round(us, 2),
                        "time": time.time()}
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            # per-writer temp name: two processes racing the same cache
            # path must never write the same temp file (a shared name
            # lets writer A replace from a file writer B is mid-write),
            # and os.replace keeps the final JSON atomic either way
            tmp = f"{path}.{os.getpid()}.{threading.get_ident()}.tmp"
            with open(tmp, "w") as f:
                json.dump({"version": 1, "entries": entries}, f, indent=1)
            os.replace(tmp, path)
        except OSError:
            pass                    # cache is best-effort
        _cache = entries
        _cache_path_loaded = path


def reset_cache_for_tests():
    """Drop the in-memory cache so a changed $REPRO_AUTOTUNE_CACHE is
    picked up (tests point it at tmp dirs)."""
    global _cache, _cache_path_loaded
    with _lock:
        _cache = None
        _cache_path_loaded = None


# ---------------------------------------------------------------------------
# keys and heuristics
# ---------------------------------------------------------------------------

def shape_bucket(shape: Sequence[int]) -> Tuple[int, ...]:
    """Next power of two per dim: nearby problem sizes share a table
    entry (and a measurement)."""
    return tuple(1 if d <= 1 else 1 << (int(d) - 1).bit_length()
                 for d in shape)


def table_key(kernel: str, dtype, shape: Sequence[int],
              backend: Optional[str] = None) -> str:
    backend = backend or jax.default_backend()
    bucket = "x".join(str(d) for d in shape_bucket(shape))
    return f"{kernel}|{jnp.dtype(dtype).name}|{bucket}|{backend}"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _heuristic(kernel: str, dtype, shape: Sequence[int],
               backend: str) -> Dict[str, int]:
    on_tpu = backend == "tpu"
    itemsize = jnp.dtype(dtype).itemsize
    sublane = {1: 32, 2: 16}.get(itemsize, 8)

    if kernel == "fxp_matmul":
        M, K, N = shape
        if on_tpu:
            # MXU-aligned tiles: minor dims multiples of 128, majors of
            # the dtype sublane count; the legacy constants are the caps
            return {"block_m": min(_round_up(M, sublane), 256),
                    "block_n": min(_round_up(N, 128), 256),
                    "block_k": min(_round_up(K, 128), 512)}
        # interpret mode: one grid step if the operand blocks fit the
        # budget, else keep M/N whole and chunk K (the sequential axis)
        if M * K + K * N + M * N <= _INTERPRET_ELEMS:
            return {"block_m": M, "block_n": N, "block_k": K}
        bk = max(1, _INTERPRET_ELEMS // max(M + N, 1))
        return {"block_m": M, "block_n": N, "block_k": min(K, bk)}

    if kernel == "kmeans_assign":
        N, D, K = shape
        if on_tpu:
            bn = min(_round_up(N, 8), 1024)
            while bn > 8 and bn * D + K * D + K * D > _VMEM_ELEMS:
                bn //= 2
            return {"block_n": bn}
        if N * D <= _INTERPRET_ELEMS:
            return {"block_n": N}
        return {"block_n": max(1, _INTERPRET_ELEMS // max(D, 1))}

    if kernel == "split_hist":
        N, F, nbc = shape
        if on_tpu:
            # VMEM holds the (F, nbc) accumulator and its double-buffered
            # output block, and per row block one feature's (nbc, bn)
            # iota, compare mask and f32 one-hot.  Rows run along lanes,
            # so bn is a multiple of 128 unless it covers all of N.
            bn = (_SPLIT_HIST_VMEM_ELEMS - 3 * F * nbc) // (3 * nbc)
            bn = min(1024, max(128, bn // 128 * 128))
            return {"block_n": N if N <= bn else bn}
        # interpret mode: bound the F one-hots of a grid step together
        bn = max(1, _ONEHOT_ELEMS // max(F * nbc, 1))
        return {"block_n": min(N, bn, 1024)}

    raise ValueError(f"unknown kernel {kernel!r}")


def block_shapes(kernel: str, dtype, shape: Sequence[int],
                 backend: Optional[str] = None) -> Dict[str, int]:
    """Measured-or-heuristic block shapes for one kernel call.

    Consults the on-disk table first (measured entries win), then the
    per-backend heuristic.  Measured entries are clamped to the actual
    shape — a table tuned at bucket size 512 must not hand a 512-wide
    block to a 300-row call.

    >>> block_shapes("fxp_matmul", "int8", (64, 128, 32),
    ...              backend="cpu")
    {'block_m': 64, 'block_n': 32, 'block_k': 128}
    """
    backend = backend or jax.default_backend()
    entry = _load_cache().get(table_key(kernel, dtype, shape, backend))
    if entry is not None:
        blocks = dict(entry["blocks"])
    else:
        blocks = _heuristic(kernel, dtype, shape, backend)
    for name, axis in KERNEL_DIMS[kernel].items():
        blocks[name] = max(1, min(int(blocks[name]), int(shape[axis])))
    return blocks


# ---------------------------------------------------------------------------
# measured autotuning
# ---------------------------------------------------------------------------

# Declarative candidate sets, keyed kernel -> backend (with a "default"
# fallback row shared by every backend without its own).  Entry values:
# an int is literal, a dim name (see _DIM_NAMES) takes that dimension's
# full extent, and ["heur", f] scales the heuristic's value by f.  The
# per-backend heuristic is always candidate 0; everything here is
# clamped to the problem shape and deduplicated before timing.
CANDIDATE_TABLE: Dict[str, Dict[str, tuple]] = {
    "fxp_matmul": {
        "default": (
            {"block_m": 256, "block_n": 256, "block_k": 512},
            {"block_m": 128, "block_n": 128, "block_k": 512},
            {"block_m": "M", "block_n": "N", "block_k": "K"},
            {"block_m": "M", "block_n": "N", "block_k": 1024},
        ),
    },
    "kmeans_assign": {
        "default": (
            {"block_n": "N"},
            {"block_n": ["heur", 2]},
            {"block_n": ["heur", 0.5]},
            {"block_n": 512},
            {"block_n": 128},
        ),
    },
    "split_hist": {
        "default": (
            {"block_n": "N"},
            {"block_n": ["heur", 2]},
            {"block_n": ["heur", 0.5]},
            {"block_n": 512},
            {"block_n": 128},
        ),
    },
}


def register_candidates(kernel: str, candidates: Sequence[dict], *,
                        backend: str = "default") -> None:
    """Extend the candidate table at runtime (a new backend's tile
    sweep, a workload-specific shape family) — same symbolic entry
    format as ``CANDIDATE_TABLE``."""
    if kernel not in KERNEL_DIMS:
        raise ValueError(f"unknown kernel {kernel!r}")
    table = CANDIDATE_TABLE.setdefault(kernel, {})
    table[backend] = tuple(dict(c) for c in candidates)


def _resolve_entry(kernel: str, entry: dict, heur: Dict[str, int],
                   shape: Sequence[int]) -> Dict[str, int]:
    out = {}
    names = _DIM_NAMES[kernel]
    for block, val in entry.items():
        if isinstance(val, str):
            val = shape[names[val]]
        elif isinstance(val, (list, tuple)):
            tag, factor = val
            assert tag == "heur", f"unknown candidate op {tag!r}"
            val = heur[block] * factor
        out[block] = max(1, int(val))
    return out


def _candidates(kernel: str, dtype, shape: Sequence[int],
                backend: str) -> list:
    heur = _heuristic(kernel, dtype, shape, backend)
    table = CANDIDATE_TABLE.get(kernel, {})
    rows = table.get(backend, table.get("default", ()))
    cands = [heur] + [_resolve_entry(kernel, e, heur, shape)
                      for e in rows]
    # clamp + dedup, preserving order
    dims = KERNEL_DIMS[kernel]
    out, seen = [], set()
    for c in cands:
        c = {k: max(1, min(int(v), int(shape[dims[k]])))
             for k, v in c.items()}
        key = tuple(sorted(c.items()))
        if key not in seen:
            seen.add(key)
            out.append(c)
    return out


def _time_call(fn, iters: int = 3) -> float:
    jax.block_until_ready(fn())            # compile + warm
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def _bench_harness(kernel: str, shape: Sequence[int], dtype,
                   interpret: Optional[bool]):
    """Representative inputs + a ``run(blocks)`` closure for one
    kernel.  Returns ``(dtype, run)``."""
    from repro.kernels import fxp_matmul as _fxp
    from repro.kernels import kmeans_assign as _km
    from repro.kernels import split_hist as _sh
    from repro.kernels.ops import interpret_mode

    interpret = interpret_mode() if interpret is None else interpret
    rng = np.random.default_rng(0)

    if kernel == "fxp_matmul":
        dtype = dtype or jnp.int8
        M, K, N = shape
        a = jnp.asarray(rng.integers(-100, 100, (M, K)), dtype)
        b = jnp.asarray(rng.integers(-100, 100, (K, N)), dtype)

        def run(blocks):
            return jax.jit(lambda a, b: _fxp.fxp_matmul(
                a, b, interpret=interpret, **blocks))(a, b)
    elif kernel == "kmeans_assign":
        dtype = dtype or jnp.float32
        N, D, K = shape
        x = jnp.asarray(rng.normal(size=(N, D)), dtype)
        c = jnp.asarray(rng.normal(size=(K, D)), dtype)
        w = jnp.ones((N,), jnp.float32)

        def run(blocks):
            return jax.jit(lambda x, c, w: _km.kmeans_assign(
                x, c, w, interpret=interpret, **blocks))(x, c, w)
    elif kernel == "split_hist":
        dtype = dtype or jnp.float32
        N, F, nbc = shape
        n_nodes, n_bins, n_classes = 1, max(1, nbc), 1
        node = jnp.zeros((N,), jnp.int32)
        xb = jnp.asarray(rng.integers(0, n_bins, (N, F)), jnp.int32)
        y = jnp.zeros((N,), jnp.int32)
        w = jnp.ones((N,), jnp.float32)

        def run(blocks):
            return jax.jit(lambda n_, x_, y_, w_: _sh.split_hist(
                n_, x_, y_, w_, n_nodes=n_nodes, n_bins=n_bins,
                n_classes=n_classes, interpret=interpret, **blocks))(
                    node, xb, y, w)
    else:
        raise ValueError(f"unknown kernel {kernel!r}")
    return dtype, run


def measure_candidates(kernel: str, shape: Sequence[int], dtype=None,
                       *, interpret: Optional[bool] = None
                       ) -> List[Measurement]:
    """Time every candidate block shape for ``(kernel, shape)`` on this
    backend and return the raw timings as :class:`Measurement` records
    — the same rows the plan controller's trace speaks, so kernel-level
    and plan-level tuning decisions are directly comparable.  Candidates
    that fail to lower are skipped; when none lowers, the last failure
    is raised."""
    backend = jax.default_backend()
    dtype, run = _bench_harness(kernel, shape, dtype, interpret)
    tkey = table_key(kernel, dtype, shape, backend)
    out: List[Measurement] = []
    failure: Optional[Exception] = None
    for blocks in _candidates(kernel, dtype, shape, backend):
        try:
            us = _time_call(lambda b=blocks: run(b))
        except Exception as e:      # a candidate may not lower — skip it
            failure = e
            continue
        out.append(Measurement(
            key=(kernel, tkey, tuple(sorted(blocks.items()))),
            seconds=us * 1e-6, steps=1, source="autotune"))
    if not out:
        raise RuntimeError(
            f"no {kernel} block shape lowers for shape {tuple(shape)} on "
            f"{backend}") from failure
    return out


def autotune(kernel: str, shape: Sequence[int], dtype=None,
             *, interpret: Optional[bool] = None) -> Dict[str, int]:
    """Measure candidate block shapes for ``(kernel, shape)`` on this
    backend, persist the winner, and return it.

    ``shape`` is the kernel's logical problem shape: ``(M, K, N)`` for
    ``fxp_matmul``, ``(N, D, K)`` for ``kmeans_assign``,
    ``(N, F, n_nodes*n_bins*n_classes)`` for ``split_hist``.
    """
    backend = jax.default_backend()
    dtype_r, _ = _bench_harness(kernel, shape, dtype, interpret)
    measured = measure_candidates(kernel, shape, dtype,
                                  interpret=interpret)
    best = min(measured, key=lambda m: m.seconds)
    best_blocks = dict(best.key[2])
    _store(table_key(kernel, dtype_r, shape, backend), best_blocks,
           best.seconds * 1e6)
    return best_blocks
