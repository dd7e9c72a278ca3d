"""Serving launcher: the PIM prediction path end to end.

Trains (or restores) a workload, publishes it through the
:class:`~repro.serving.ModelRegistry`, stands up the micro-batching
queue, fires a burst of synthetic single-row requests, and prints the
latency/throughput summary — the CLI twin of ``benchmarks/
bench_serving.py``'s smoke cells.

  PYTHONPATH=src python -m repro.launch.serve --workload linreg \\
      --precision int8 --requests 512 --rate 2000

With ``--ckpt-dir`` the registry restores the newest valid Trainer
checkpoint (sha256-validated) instead of training in-process.
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import datasets, make_cpu_grid
from repro.core.mlalgos import api
from repro.core.mlalgos.kmeans import KMeans
from repro.core.mlalgos.linreg import LinReg
from repro.core.mlalgos.multinomial import MultinomialLogReg
from repro.core.mlalgos.svm import LinearSVM
from repro.serving import MicroBatchQueue, ModelRegistry
from repro.compile_cache import place_compile_cache


def build_workload(name: str, precision: str):
    if name == "linreg":
        return LinReg(lr=0.05, precision=precision)
    if name == "svm":
        return LinearSVM(lr=0.05, precision=precision)
    if name == "multinomial":
        return MultinomialLogReg(n_classes=4, lr=0.2,
                                 precision=precision, softmax="lut")
    if name == "kmeans":
        return KMeans(k=8, precision=precision)
    raise SystemExit(f"unknown workload {name!r}")


def make_problem(name: str, rows: int, features: int):
    key = jax.random.PRNGKey(0)
    if name == "multinomial":
        X = jax.random.normal(key, (rows, features))
        y = jax.random.randint(jax.random.PRNGKey(1), (rows,), 0, 4)
        return X, y
    X, y, _ = datasets.regression(key, rows, features)
    if name == "svm":
        y = (np.asarray(y) > 0).astype(np.float32)
    if name == "kmeans":
        y = None
    return X, y


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="linreg",
                    choices=["linreg", "svm", "multinomial", "kmeans"])
    ap.add_argument("--precision", default="fp32",
                    choices=["fp32", "int16", "int8"])
    ap.add_argument("--rows", type=int, default=512)
    ap.add_argument("--features", type=int, default=16)
    ap.add_argument("--train-steps", type=int, default=20)
    ap.add_argument("--requests", type=int, default=256)
    ap.add_argument("--rate", type=float, default=1000.0,
                    help="offered load, requests/s (open loop)")
    ap.add_argument("--max-batch", type=int, default=32)
    ap.add_argument("--max-wait-ms", type=float, default=2.0)
    ap.add_argument("--ckpt-dir", default=None,
                    help="restore the newest valid Trainer checkpoint "
                         "instead of training in-process")
    args = ap.parse_args()

    wl = build_workload(args.workload, args.precision)
    X, y = make_problem(args.workload, args.rows, args.features)
    grid = make_cpu_grid(8)

    if args.workload == "multinomial":
        template = jnp.zeros((args.features, 4))
    elif args.workload == "kmeans":
        template = jnp.zeros((8, args.features))
    else:
        template = jnp.zeros((args.features,))
    reg = ModelRegistry(wl, template, ckpt_dir=args.ckpt_dir, grid=grid)
    if args.ckpt_dir is not None:
        version = reg.refresh()
        if version is None:
            raise SystemExit(f"no valid checkpoint in {args.ckpt_dir}")
        print(f"restored checkpoint step {version}")
    else:
        state = api.fit(wl, grid, X, y, steps=args.train_steps).state
        reg.publish(state, version=0)

    _, runner = reg.current()
    runner.warmup(args.features)
    q = MicroBatchQueue(reg, max_batch=args.max_batch,
                        max_wait_ms=args.max_wait_ms)

    Xn = np.asarray(X, np.float32)
    gap = 1.0 / args.rate
    tickets = []
    t0 = time.perf_counter()
    for i in range(args.requests):
        target = t0 + i * gap
        while time.perf_counter() < target:
            pass
        tickets.append(q.submit(Xn[i % Xn.shape[0]], block=True))
    for t in tickets:
        t.get(timeout=60.0)
    dt = time.perf_counter() - t0
    q.close()

    s = q.stats()
    c = runner.counters()
    print(f"{args.workload}/{args.precision}: {s['requests']} requests "
          f"at {args.rate:.0f} req/s offered -> "
          f"{s['requests'] / dt:.0f} req/s served, "
          f"p50 {s['p50_ms']:.2f} ms, p99 {s['p99_ms']:.2f} ms, "
          f"mean batch {s['mean_batch']:.1f}, "
          f"compile misses {c['compile_misses']} "
          f"(steady {c['steady_compile_misses']})")


if __name__ == "__main__":
    place_compile_cache()
    main()
