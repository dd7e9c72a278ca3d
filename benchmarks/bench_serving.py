"""Serving table: micro-batched latency under load x saturation ceiling.

The serving layer (``repro/serving``) promises two kinds of numbers
this benchmark pins as artifacts:

  * ``serving`` cells — one open-loop request burst per (workload x
    precision x offered load): single-row requests are fired at the
    :class:`MicroBatchQueue` at a fixed offered rate and the cell
    records enqueue→result latency (p50/p99 ms), served throughput,
    and the mean coalesced batch size.  Light load should pay at most
    one ``max_wait_ms`` deadline of latency; heavy load should serve
    near-full buckets.
  * ``saturation`` cells — the queue-free ceiling per (workload x
    precision): :meth:`PredictRunner.run_stream` drains a stream of
    top-bucket batches with double-buffered staging, giving rows/s
    with zero queueing overhead.  The serving cells' throughput can
    approach but never beat this number.

Every cell asserts the warm-cache claim: after :meth:`warmup` the
bucket ladder is closed, so ``steady_compile_misses`` must be 0 — a
nonzero count means request traffic found a shape the ladder missed,
the serving analogue of the training engine's retrace bug.

Schema ``bench_serving/v1`` — a family beside ``bench_scaling`` /
``bench_streaming``; ``tools/bench_diff.py`` judges completeness from
this artifact's own config (``serve_workloads`` x ``serve_precisions``
x ``serve_loads``), enforces the zero-steady-miss gate, and treats p99
latency as the regression metric (lower is better — the inverse of the
throughput families).

Usage::

    PYTHONPATH=src python benchmarks/bench_serving.py           # full
    PYTHONPATH=src python benchmarks/bench_serving.py --smoke   # CI
    PYTHONPATH=src python benchmarks/bench_serving.py --out p.json
"""

import argparse
import json
import os
import sys
import time

import jax
import numpy as np

if __package__ in (None, ""):      # `python benchmarks/bench_serving.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from repro.core import make_cpu_grid
from repro.core.mlalgos import api
from repro.core.mlalgos.linreg import LinReg
from repro.core.mlalgos.multinomial import MultinomialLogReg
from repro.core.mlalgos.svm import LinearSVM
from repro.serving import MicroBatchQueue, PredictRunner
from repro.compile_cache import place_compile_cache

# the sweep axes (config promises = exactly these; bench_diff checks)
WORKLOADS = ("linreg", "svm", "multinomial")
PRECISIONS = ("fp32", "int8")
LOADS_FULL = (500, 2000, 8000)      # offered requests/s, open loop
LOADS_SMOKE = (500, 2000)


def make_workload(name, precision):
    return {
        "linreg": lambda: LinReg(lr=0.05, precision=precision),
        "svm": lambda: LinearSVM(lr=0.05, precision=precision),
        "multinomial": lambda: MultinomialLogReg(
            n_classes=4, lr=0.2, precision=precision),
    }[name]()


def make_problem(name, rows, features, seed=0):
    key = jax.random.PRNGKey(seed)
    X = jax.random.normal(key, (rows, features))
    if name == "multinomial":
        y = jax.random.randint(jax.random.PRNGKey(seed + 1),
                               (rows,), 0, 4)
    elif name == "svm":
        y = (jax.random.normal(jax.random.PRNGKey(seed + 1),
                               (rows,)) > 0).astype(np.float32)
    else:
        y = jax.random.normal(jax.random.PRNGKey(seed + 1), (rows,))
    return X, y


def build_runner(name, precision, grid, *, rows, features, train_steps):
    """Train briefly and stand up a warmed PredictRunner — the model
    state is an argument of the compiled forward, so its values do not
    matter for timing, only its shapes."""
    wl = make_workload(name, precision)
    X, y = make_problem(name, rows, features)
    state = api.fit(wl, grid, X, y, steps=train_steps).state
    runner = PredictRunner(wl, state, grid=grid)
    runner.warmup(features)
    return runner


def serve_cell(name, precision, runner, *, load, requests, features,
               max_batch, max_wait_ms):
    """One open-loop burst: fire ``requests`` single-row requests at
    ``load`` req/s through the micro-batching queue."""
    q = MicroBatchQueue(runner, max_batch=max_batch,
                        max_wait_ms=max_wait_ms)
    rows = np.random.default_rng(0).standard_normal(
        (256, features)).astype(np.float32)
    gap = 1.0 / load
    tickets = []
    t0 = time.perf_counter()
    for i in range(requests):
        target = t0 + i * gap
        while time.perf_counter() < target:
            pass
        tickets.append(q.submit(rows[i % rows.shape[0]], block=True))
    for t in tickets:
        t.get(timeout=60.0)
    dt = time.perf_counter() - t0
    q.close()
    s = q.stats()
    c = runner.counters()
    assert c["steady_compile_misses"] == 0, \
        f"steady-state compile miss in {name}/{precision}: {c}"
    cell = {
        "workload": name, "precision": precision, "offered_rps": load,
        "requests": s["requests"],
        "throughput_rps": round(s["requests"] / dt, 1),
        "p50_ms": round(s["p50_ms"], 3),
        "p99_ms": round(s["p99_ms"], 3),
        "mean_batch": round(s["mean_batch"], 2),
        "batches": s["batches"],
        "steady_compile_misses": c["steady_compile_misses"],
    }
    print(f"serve {name:11s} {precision:4s} offered={load:6d} rps  "
          f"served {cell['throughput_rps']:8.1f} rps  "
          f"p50 {cell['p50_ms']:7.3f} ms  p99 {cell['p99_ms']:7.3f} ms  "
          f"batch {cell['mean_batch']:5.2f}", flush=True)
    return cell


def saturation_cell(name, precision, runner, *, features, batches=48):
    """Queue-free ceiling: drain top-bucket batches through
    ``run_stream`` (double-buffered staging) and report rows/s."""
    top = runner.buckets[-1]
    rng = np.random.default_rng(1)
    feed = [rng.standard_normal((top, features)).astype(np.float32)
            for _ in range(batches)]
    for out in runner.run_stream(feed[:4]):     # warmup the stream path
        jax.block_until_ready(out)
    t0 = time.perf_counter()
    for out in runner.run_stream(feed):
        jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    c = runner.counters()
    assert c["steady_compile_misses"] == 0, \
        f"steady-state compile miss in {name}/{precision}: {c}"
    cell = {
        "workload": name, "precision": precision,
        "batch_rows": top, "batches": batches,
        "rows_per_s": round(batches * top / dt, 1),
        "steady_compile_misses": c["steady_compile_misses"],
    }
    print(f"saturate {name:11s} {precision:4s} "
          f"{cell['rows_per_s']:12.1f} rows/s "
          f"({top} rows x {batches} batches)", flush=True)
    return cell


def run(*, smoke: bool = False, out: str = "BENCH_serving.json"):
    n_vdpus = 8
    rows = 2048 if smoke else 4096
    features = 32
    train_steps = 10 if smoke else 30
    requests = 256 if smoke else 1024
    max_batch, max_wait_ms = 32, 2.0
    loads = LOADS_SMOKE if smoke else LOADS_FULL

    grid = make_cpu_grid(n_vdpus)
    serving, saturation = [], []
    for name in WORKLOADS:
        for precision in PRECISIONS:
            runner = build_runner(name, precision, grid, rows=rows,
                                  features=features,
                                  train_steps=train_steps)
            for load in loads:
                serving.append(serve_cell(
                    name, precision, runner, load=load,
                    requests=requests, features=features,
                    max_batch=max_batch, max_wait_ms=max_wait_ms))
            saturation.append(saturation_cell(
                name, precision, runner, features=features,
                batches=16 if smoke else 48))

    result = {
        "schema": "bench_serving/v1",
        "config": {
            "backend": jax.default_backend(),
            "n_devices": len(jax.devices()),
            "smoke": smoke,
            "rows": rows, "features": features, "n_vdpus": n_vdpus,
            "requests": requests,
            "max_batch": max_batch, "max_wait_ms": max_wait_ms,
            "serve_workloads": list(WORKLOADS),
            "serve_precisions": list(PRECISIONS),
            "serve_loads": list(loads),
        },
        "serving": serving,
        "saturation": saturation,
    }
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {os.path.abspath(out)} ({len(serving)} serving "
          f"cells, {len(saturation)} saturation cells)", flush=True)
    return result


if __name__ == "__main__":
    place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-size sweep (fewer requests / loads)")
    ap.add_argument("--out", default="BENCH_serving.json")
    args = ap.parse_args()
    run(smoke=args.smoke, out=args.out)
