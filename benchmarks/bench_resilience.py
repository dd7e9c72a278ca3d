"""Resilience table: armed-but-idle overhead x recovery latency.

The fault-tolerant runtime (``repro.resilience``) promises two numbers
this benchmark pins as artifacts:

  * ``pipeline="armed"`` cells — the SAME linreg fit as the baseline
    cells, but dispatched through the resilient driver with an *empty*
    ``FaultPlan`` armed.  The compiled bodies are byte-identical (the
    driver only re-chunks the host dispatch loop), so the armed-idle
    overhead — ``(armed - baseline) / baseline`` — is the full price of
    carrying fault tolerance when nothing faults.  Acceptance: < 2% in
    the merge-dominated regime (large grids; tiny grids are dispatch-
    bound on CPU and the chunking shows).
  * ``recovery`` rows — one injected fault per row (NaN-poisoned lane,
    dispatch timeout), recovered by ``RecoveryPolicy`` rollback.
    ``recovery_latency_s`` is the driver's measured fail-to-resume wall
    time (backoff + checkpoint restore + mask replacement), straight
    from the trace the driver writes to ``tuning_trace["recovery"]``.

Schema ``bench_resilience/v1`` — a new family beside ``bench_scaling``;
``tools/bench_diff.py`` gates it with the same generic promises
(``config.pipelines`` x ``config.pipeline_precisions`` spans the
baseline/armed pair) plus section completeness for ``recovery``.

Usage::

    PYTHONPATH=src python benchmarks/bench_resilience.py           # full
    PYTHONPATH=src python benchmarks/bench_resilience.py --smoke   # CI
    PYTHONPATH=src python benchmarks/bench_resilience.py --out p.json
"""

import argparse
import json
import os
import sys
import tempfile

import jax
import numpy as np

if __package__ in (None, ""):           # `python benchmarks/bench_resilience.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks.common import time_fn
from repro.core import datasets, make_cpu_grid
from repro.core.mlalgos import make_linreg_step
from repro.resilience import (FaultEvent, FaultPlan, RecoveryPolicy,
                              drive_fit, faults)
from repro.distributed.merge_plan import MergePlan
from repro.compile_cache import place_compile_cache

VDPUS_FULL = (16, 64, 256)
VDPUS_SMOKE = (4, 16)
CADENCES = (1, 4)
PIPELINES = ("baseline", "armed")
# one recovery row per injected-fault shape; wire_bitflip is excluded
# on purpose — a sub-threshold flip is absorbed without a restart, so
# it has no recovery latency to measure
RECOVERY_KINDS = ("nan_lane", "timeout")
RECOVERY_STEPS = 32
RECOVERY_CADENCE = 4


def _cell(v, k, pname, us_step, **extra):
    cell = {
        "algo": "linreg", "workload": "linreg", "batch_size": "full",
        "mesh": "none", "n_vdpus": v, "precision": "fp32",
        "merge_every": k, "pipeline": pname, "plan": "avg",
        "us_per_step": round(us_step, 2),
        "steps_per_s": round(1e6 / us_step, 1),
    }
    cell.update(extra)
    return cell


def overhead_sweep(vdpus, cadences, X, y, *, timed_steps, warmup,
                   iters):
    """Baseline vs armed-but-idle steps/s per (n_vdpus, merge_every).
    The armed cells run under ``faults.armed`` with a zero-event
    FaultPlan — the resilient driver's full dispatch path, nothing to
    inject — so the delta IS the runtime's idle tax."""
    idle = FaultPlan(events=(), seed=0)
    cells = []
    for v in vdpus:
        grid = make_cpu_grid(v)
        data, n, local_fn, update_fn, w0 = make_linreg_step(
            grid, X, y, lr=0.05)
        for k in cadences:
            base_us = time_fn(
                lambda k=k: grid.fit(
                    init_state=w0, local_fn=local_fn,
                    update_fn=update_fn, data=data, steps=timed_steps,
                    merge_every=k),
                warmup=warmup, iters=iters) / timed_steps

            def armed_fit(k=k):
                with faults.armed(idle):
                    return grid.fit(
                        init_state=w0, local_fn=local_fn,
                        update_fn=update_fn, data=data,
                        steps=timed_steps, merge_every=k)
            armed_us = time_fn(armed_fit, warmup=warmup,
                               iters=iters) / timed_steps
            overhead = (armed_us - base_us) / base_us
            cells.append(_cell(v, k, "baseline", base_us))
            cells.append(_cell(v, k, "armed", armed_us,
                               armed_overhead_pct=round(
                                   100.0 * overhead, 2)))
            print(f"linreg v={v:5d} k={k:2d}  baseline "
                  f"{1e6 / base_us:9.1f} steps/s  armed "
                  f"{1e6 / armed_us:9.1f} steps/s  overhead "
                  f"{100 * overhead:+6.2f}%", flush=True)
    return cells


def recovery_sweep(v, X, y):
    """Measured fail-to-resume latency per fault kind: one event mid-
    run, recovered through rollback to the last validated checkpoint.
    ``recovery_latency_s`` comes from the driver's own trace (the
    ``latency_s`` it stamps on every rollback decision)."""
    rows = []
    grid = make_cpu_grid(v)
    data, n, local_fn, update_fn, w0 = make_linreg_step(
        grid, X, y, lr=0.05)
    recovery = RecoveryPolicy(backoff_base_s=0.01, backoff_max_s=0.05)
    for kind in RECOVERY_KINDS:
        mid = RECOVERY_STEPS // RECOVERY_CADENCE // 2
        if kind == "timeout":
            ev = FaultEvent(mid, "timeout", duration_s=0.05)
        else:
            ev = FaultEvent(mid, kind, lane=1)
        fp = FaultPlan(events=(ev,), seed=0)
        with tempfile.TemporaryDirectory() as ckpt_dir:
            state, history, report = drive_fit(
                grid, init_state=w0, local_fn=local_fn,
                update_fn=update_fn, data=data, steps=RECOVERY_STEPS,
                plan=MergePlan(cadence=RECOVERY_CADENCE),
                fault_plan=fp, recovery=recovery, ckpt=ckpt_dir,
                ckpt_every_rounds=2)
        latencies = [e["latency_s"] for e in report["trace"]
                     if e["action"] == "rollback"]
        row = {
            "kind": kind, "n_vdpus": v, "steps": RECOVERY_STEPS,
            "merge_every": RECOVERY_CADENCE,
            "restarts": report["restarts"],
            "recovery_latency_s": round(float(np.mean(latencies)), 4)
            if latencies else 0.0,
            "final_loss": float(history[-1]["loss"]),
        }
        rows.append(row)
        print(f"recovery {kind:12s} restarts={row['restarts']}  "
              f"latency {row['recovery_latency_s']:.4f}s  "
              f"final_loss {row['final_loss']:.4f}", flush=True)
    return rows


def run(*, smoke: bool = False, out: str = "BENCH_resilience.json"):
    key = jax.random.PRNGKey(0)
    vdpus = VDPUS_SMOKE if smoke else VDPUS_FULL
    rows = 2048 if smoke else 8192
    features = 16
    timed_steps = 16
    warmup, iters = (1, 2) if smoke else (1, 3)

    X, y, _ = datasets.regression(key, rows, features)
    cells = overhead_sweep(vdpus, CADENCES, X, y,
                           timed_steps=timed_steps, warmup=warmup,
                           iters=iters)
    recovery_rows = recovery_sweep(vdpus[-1], X, y)

    result = {
        "schema": "bench_resilience/v1",
        "config": {
            "backend": jax.default_backend(),
            "n_devices": len(jax.devices()),
            "smoke": smoke,
            "rows": rows, "features": features,
            "timed_steps": timed_steps,
            "n_vdpus": list(vdpus),
            "merge_every": list(CADENCES),
            "precisions": ["fp32"],
            "pipelines": list(PIPELINES),
            "pipeline_precisions": ["fp32"],
            "recovery_kinds": list(RECOVERY_KINDS),
            "recovery_steps": RECOVERY_STEPS,
            "recovery_merge_every": RECOVERY_CADENCE,
        },
        "throughput": cells,
        "recovery": recovery_rows,
    }
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {os.path.abspath(out)} ({len(cells)} throughput "
          f"cells, {len(recovery_rows)} recovery rows)", flush=True)
    return result


if __name__ == "__main__":
    place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-size sweep (n_vdpus <= 16)")
    ap.add_argument("--out", default="BENCH_resilience.json")
    args = ap.parse_args()
    run(smoke=args.smoke, out=args.out)
