"""Streaming-ingestion table: rotation throughput x ingest overlap.

The out-of-core layer (``data/pipeline``) promises two numbers this
benchmark pins as artifacts:

  * ``streaming`` cells — a full out-of-core fit per (workload x
    partition size x prefetch depth): steps/s at equal epochs, plus the
    driver's measured ``ingest_overlap_fraction`` — the share of
    steady-state ingest (host gather + H2D placement) hidden behind the
    compiled scan's compute (``1 - stall/ingest``, pipeline-fill
    windows excluded).  Acceptance: depth >= 2 hides >= 80% of the
    measured transfer time; ``depth=0`` is the synchronous-fetch
    floor (overlap 0 by construction — every byte is exposed).
  * ``baseline`` cells — the fully-resident minibatch fit with
    ``batch_size`` = the rotation's per-vDPU window size, same seed
    machinery: identical per-step math (same rows per step, same
    unbiased scaling), the dataset just never leaves the device.  The
    streaming/baseline ratio is the *residency tax* at each partition
    size.

Schema ``bench_streaming/v1`` — a family beside ``bench_scaling`` /
``bench_resilience``; ``tools/bench_diff.py`` judges completeness from
this artifact's own config (``stream_workloads`` x
``stream_partition_rows`` x ``stream_depths``) and gates the overlap
floor.

Usage::

    PYTHONPATH=src python benchmarks/bench_streaming.py           # full
    PYTHONPATH=src python benchmarks/bench_streaming.py --smoke   # CI
    PYTHONPATH=src python benchmarks/bench_streaming.py --out p.json
"""

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

if __package__ in (None, ""):      # `python benchmarks/bench_streaming.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from repro.core import datasets, make_cpu_grid
from repro.core.mlalgos import api
from repro.core.mlalgos.linreg import LinReg
from repro.core.mlalgos.logreg import LogReg
from repro.core.mlalgos.svm import LinearSVM
from repro.data import StreamingDataset
from repro.compile_cache import place_compile_cache

# the sweep axes (config promises = exactly these; bench_diff checks)
DEPTHS_FULL = (0, 1, 2, 4)
DEPTHS_SMOKE = (0, 2, 4)
WORKLOADS_FULL = ("linreg", "svm", "logreg")
WORKLOADS_SMOKE = ("linreg", "svm")


def make_workload(name):
    return {
        "linreg": lambda: LinReg(lr=0.05),
        "svm": lambda: LinearSVM(lr=0.05),
        "logreg": lambda: LogReg(lr=0.2),
    }[name]()


def _window_mb(rotation) -> float:
    host = rotation.window_host(0)
    return sum(np.asarray(v).nbytes for v in host.values()) / 2 ** 20


def stream_cell(name, grid, Xn, yn, *, partition_rows, depth, spw,
                epochs, seed=0):
    wl = make_workload(name)
    labels = None if name == "kmeans" else yn

    def fit(depth_, steps_, ms=None):
        sd = StreamingDataset(Xn, labels, partition_rows=partition_rows,
                              prefetch_depth=depth_,
                              steps_per_window=spw, seed=seed)
        return api.fit(wl, grid, sd, steps=steps_, merge_state=ms)

    probe = StreamingDataset(Xn, labels, partition_rows=partition_rows,
                             prefetch_depth=depth, steps_per_window=spw,
                             seed=seed)
    rotation = wl.bind_stream(grid, probe).data
    steps = epochs * rotation.windows_per_epoch * spw
    fit(depth, spw)                              # warmup: compile
    ms: dict = {}
    t0 = time.perf_counter()
    res = fit(depth, steps, ms)
    jax.block_until_ready(res.state)
    dt = time.perf_counter() - t0
    stats = ms["streaming_trace"]
    cell = {
        "workload": name, "partition_rows": partition_rows,
        "prefetch_depth": depth, "steps": steps,
        "steps_per_window": spw,
        "windows": stats["windows"],
        "steps_per_s": round(steps / dt, 1),
        "ingest_overlap_fraction": round(
            stats["ingest_overlap_fraction"], 4),
        "ingest_s": round(stats["ingest_s"], 4),
        "stall_s": round(stats["stall_s"], 4),
        "window_mb": round(_window_mb(rotation), 3),
        "final_loss": float(res.history[-1]["loss"]),
    }
    print(f"stream {name:7s} part={partition_rows:6d} depth={depth}  "
          f"{cell['steps_per_s']:8.1f} steps/s  overlap "
          f"{cell['ingest_overlap_fraction']:.3f}  "
          f"stall {cell['stall_s']:.3f}s / ingest "
          f"{cell['ingest_s']:.3f}s", flush=True)
    return cell


def baseline_cell(name, grid, X, y, *, partition_rows, steps, spw,
                  seed=0):
    """Fully-resident minibatch at batch_size = the rotation's per-vDPU
    window: the same per-step math with zero ingest."""
    wl = make_workload(name)
    part = max(1, -(-partition_rows // grid.n_vdpus))
    labels = None if name == "kmeans" else y
    wl_prog = wl.bind(grid, X, labels)
    wl_prog.fit(steps=spw, batch_size=part, sample_seed=seed)  # warmup
    t0 = time.perf_counter()
    res = wl_prog.fit(steps=steps, batch_size=part, sample_seed=seed)
    jax.block_until_ready(res.state)
    dt = time.perf_counter() - t0
    cell = {
        "workload": name, "partition_rows": partition_rows,
        "batch_size": part, "steps": steps,
        "steps_per_s": round(steps / dt, 1),
        "final_loss": float(res.history[-1]["loss"]),
    }
    print(f"resident {name:7s} part={partition_rows:6d} (b={part:5d})  "
          f"{cell['steps_per_s']:8.1f} steps/s", flush=True)
    return cell


def run(*, smoke: bool = False, out: str = "BENCH_streaming.json"):
    key = jax.random.PRNGKey(0)
    n_vdpus = 16 if smoke else 64
    rows = 65536 if smoke else 131072
    features = 384 if smoke else 512
    spw = 8
    epochs = 2
    parts = (8192, 16384) if smoke else (8192, 16384, 32768)
    depths = DEPTHS_SMOKE if smoke else DEPTHS_FULL
    workloads = WORKLOADS_SMOKE if smoke else WORKLOADS_FULL

    X, y, _ = datasets.regression(key, rows, features)
    Xn, yn = np.asarray(X), np.asarray(y)
    # {0,1} labels keep svm/logreg happy on the same matrix
    yb = (yn > 0).astype(np.float32)
    grid = make_cpu_grid(n_vdpus)

    streaming, baseline = [], []
    for name in workloads:
        labels = yn if name == "linreg" else yb
        for part_rows in parts:
            probe = StreamingDataset(Xn, labels,
                                     partition_rows=part_rows,
                                     steps_per_window=spw)
            rot = make_workload(name).bind_stream(grid, probe).data
            steps = epochs * rot.windows_per_epoch * spw
            baseline.append(baseline_cell(
                name, grid, X, labels, partition_rows=part_rows,
                steps=steps, spw=spw))
            for depth in depths:
                streaming.append(stream_cell(
                    name, grid, Xn, labels, partition_rows=part_rows,
                    depth=depth, spw=spw, epochs=epochs))

    result = {
        "schema": "bench_streaming/v1",
        "config": {
            "backend": jax.default_backend(),
            "n_devices": len(jax.devices()),
            "smoke": smoke,
            "rows": rows, "features": features, "n_vdpus": n_vdpus,
            "steps_per_window": spw, "epochs": epochs,
            "stream_workloads": list(workloads),
            "stream_partition_rows": list(parts),
            "stream_depths": list(depths),
            "overlap_floor": 0.8,
            "overlap_floor_depth": 2,
        },
        "streaming": streaming,
        "baseline": baseline,
    }
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {os.path.abspath(out)} ({len(streaming)} streaming "
          f"cells, {len(baseline)} baseline cells)", flush=True)
    return result


if __name__ == "__main__":
    place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-size sweep (n_vdpus <= 16)")
    ap.add_argument("--out", default="BENCH_streaming.json")
    args = ap.parse_args()
    run(smoke=args.smoke, out=args.out)
