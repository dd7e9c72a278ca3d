"""Paper Table: strong scaling (1 -> 2,524 DPUs) x merge cadence x
precision x merge pipeline x merge plan x workload x batch size.

Reproduces the paper's strong-scaling evaluation on the vDPU grid, with
six extra axes the follow-ups make first-class:

  * ``merge_every`` — local steps between host merges (PIM-Opt,
    arXiv 2404.07164).  The paper's observation is that the host merge
    dominates once per-DPU work shrinks; cadence k amortises one merge
    over k steps, so the strong-scaling knee moves right.
  * ``precision``   — fp32 / int16 / int8 resident datasets (the
    per-precision throughput table of the evaluation follow-up,
    arXiv 2207.07886).
  * ``pipeline``    — how the merge itself runs (this repo's PR 3):
    ``baseline`` (exact, serial), ``overlap`` (double-buffered — the
    reduction of round i emitted alongside round i+1's compute, paper
    I5), ``int8`` (error-feedback-compressed wire, paper I1 applied to
    the hop) and ``overlap+int8``.  Swept for the fp32 dataset, where
    the cadence fit is meaningful on this backend; cadence alone
    amortises the merge, the pipeline axis is the first that *shrinks*
    it.
  * ``plan``        — the composed ``distributed.merge_plan`` axis
    (PR 4): ``avg`` (the default plan — identical to the base cells),
    ``slowmo`` (SlowMo outer momentum at the merge boundary),
    ``topk`` (top-k error-feedback sparsified wire: merge_bytes drops
    below the dense int8 row), ``adaptive`` (host-side cadence
    controller; its ``merge_every`` column is the *starting* cadence —
    the controller may grow it mid-fit) and ``auto`` (v5: the unified
    self-tuning controller ``fit(merge_plan="auto")`` — cost-model
    prior + measured round times pick cadence AND wire format; like
    adaptive, its ``merge_every`` column is the starting cadence and
    the u(k) fit does not apply).  Swept for fp32 cells at the
    baseline pipeline over ``plan_n_vdpus``.  The v5 acceptance row:
    auto cells must land within ~10% steps/s of the best hand-tuned
    plan cell at each ``plan_n_vdpus`` grid size.

  * ``workload`` / ``batch_size`` — the Workload-protocol axes (this
    repo's PR 5): the PIM-Opt companion workloads (linear SVM,
    multinomial logistic regression) timed through the same generic
    ``api.fit`` path as linreg, and on-device minibatch sampling
    (``batch_size < rows_per_vdpu`` processes a sampled fraction of
    each resident partition per local step — the steps/s win PIM-Opt's
    minibatch local-SGD banks).  Swept at ``workload_n_vdpus`` over
    cadences {1, 4} x ``batch_sizes``; base cells carry
    ``workload="linreg"``, ``batch_size="full"``.

  * ``mesh``        — v6: where the grid's merge actually runs.
    ``"none"`` cells are the emulated vmap grid; ``"PxD"`` cells run
    the same engine under a real ``jax.sharding.Mesh`` via shard_map
    (``core.pim.make_mesh_grid`` — P pods x D data devices,
    hierarchical psums, the pod hop compressible).  Mesh cells appear
    when the runtime has more than one device (CI forces 8 with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``); on one
    device the ``mesh_grids`` config list is empty and the promise
    adapts.
  * ``weak_scaling`` — v6: a separate section with *fixed rows per
    vDPU* (the paper's weak-scaling protocol: grow the grid, keep the
    per-DPU partition constant).  The full sweep reaches 10k+ emulated
    vDPUs; the acceptance row is ``rows_per_s`` staying within the
    same order of magnitude as the grid grows.

One sweep produces the tables plus the accuracy-vs-cadence /
accuracy-vs-pipeline / accuracy-vs-plan / accuracy-vs-workload curves,
in a single ``BENCH_scaling.json`` (schema bench_scaling/v6,
documented in docs/BENCHMARKS.md).

Merge-fraction model: the measured per-local-step time at cadence k is

    u(k) = t_local + t_merge / k

(t_local = vDPU-local compute per step, t_merge = one hierarchical
merge+resync).  Fitting u over the cadence sweep {1, 4, 16} by least
squares yields per-cell (t_local, t_merge); ``merge_fraction`` of a
cell is (t_merge/k) / u(k) — the share of a step the host hop costs at
that cadence.  ``merge_fraction_overlapped`` of an overlap cell is the
share of the *baseline* merge the pipeline hid:
1 − t_merge(pipeline)/t_merge(baseline).  ``merge_bytes`` is the
analytic wire cost of one merge round (``distributed.compression.
wire_bytes`` over the tree that crosses the hop — what the int8 wire
divides by ~4).

Usage::

    PYTHONPATH=src python benchmarks/bench_scaling.py            # full sweep
    PYTHONPATH=src python benchmarks/bench_scaling.py --smoke    # CI (n_vdpus <= 16)
    PYTHONPATH=src python benchmarks/bench_scaling.py --out path.json
"""

import argparse
import json
import os
import sys

import jax
import numpy as np

if __package__ in (None, ""):                 # `python benchmarks/bench_scaling.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from benchmarks.common import time_fn
from repro.core import datasets, make_cpu_grid, make_mesh_grid
from repro.core.mlalgos import (make_linreg_step, train_linreg,
                                train_logreg)
from repro.core.mlalgos.linreg import closed_form
from repro.core.mlalgos.logreg import accuracy
from repro.core.mlalgos.svm import svm_accuracy
from repro.core.mlalgos.multinomial import multinomial_accuracy
from repro.distributed import compression as comp
from repro.distributed.merge_plan import (MergePlan, SlowMo,
                                          AdaptiveCadence)
from repro.compile_cache import place_compile_cache

VDPUS_FULL = (1, 4, 16, 64, 256, 1024, 2048)
VDPUS_SMOKE = (1, 4, 16)
# the plan axis costs one extra cadence sweep per plan, so the full
# sweep samples it at a small and a merge-dominated grid size
PLAN_VDPUS_FULL = (64, 1024)
CADENCES = (1, 4, 16)
PRECISIONS = ("fp32", "int16", "int8")
# (name, overlap_merge, compression bits); swept for fp32 cells
PIPELINES = (("baseline", False, 0), ("overlap", True, 0),
             ("int8", False, 8), ("overlap+int8", True, 8))
# composed merge plans (PR 4; "auto" is v5), swept for fp32 cells at
# the baseline pipeline; "avg" is the base cells' plan label
PLANS = ("slowmo", "topk", "adaptive", "auto")
TOPK_FRAC = 0.125
# the Workload-protocol axis (v4): estimators timed through api.fit and
# the minibatch sampling sizes ("full" = batch_size=None, the exact
# engine; ints = rows sampled per vDPU per local step)
WORKLOADS = ("linreg", "svm", "multinomial")
WORKLOAD_CADENCES = (1, 4)
BATCH_SIZES = ("full", 32)
WORKLOAD_VDPUS_FULL = (64,)
# v6: real-mesh cells (shard_map engine) — only generated when the
# runtime has > 1 device; int8 is the pipeline whose wire actually
# crosses the pod hop compressed
MESH_VDPUS_FULL = (64, 256)
MESH_VDPUS_SMOKE = (16,)
MESH_PIPELINES = ("baseline", "int8")
# v6: weak scaling — fixed rows per vDPU, growing grid
WEAK_VDPUS_FULL = (1024, 4096, 10240)
WEAK_VDPUS_SMOKE = (64, 256)
WEAK_ROWS_PER_VDPU = 16
WEAK_FEATURES = 8
WEAK_MERGE_EVERY = 4


def _mesh_grid_or_none(v: int):
    """A mesh grid for ``v`` vDPUs, or None when the runtime cannot
    host one (single device, or ``v`` not divisible by the shard
    count).  Two pods when the device count is even — the pod axis is
    the compressible "host hop" — one otherwise."""
    n_dev = len(jax.devices())
    if n_dev < 2:
        return None
    pods = 2 if n_dev % 2 == 0 else 1
    if v % n_dev:
        return None
    return make_mesh_grid(v, pods=pods)


def _mesh_label(grid) -> str:
    if grid is None or grid.mesh is None:
        return "none"
    return "x".join(str(grid.mesh.shape[a]) for a in grid.data_axes)


def _compression(bits: int):
    return comp.CompressionConfig(bits=bits) if bits else None


def _plan(pname: str, k: int) -> MergePlan:
    if pname == "slowmo":
        return MergePlan(cadence=k, outer=SlowMo(beta=0.5))
    if pname == "topk":
        return MergePlan(cadence=k, compression=comp.CompressionConfig(
            bits=8, top_k_frac=TOPK_FRAC))
    if pname == "adaptive":
        return MergePlan(cadence=k, outer=AdaptiveCadence(k_max=32))
    if pname == "auto":
        from repro.tuning import AutoTune
        return MergePlan(cadence=k, outer=AutoTune(k_max=32))
    if pname in ("avg", "int8"):
        return MergePlan(cadence=k, compression=_compression(
            8 if pname == "int8" else 0))
    raise ValueError(pname)


def _fit_merge_model(cadences, us_per_step):
    """Least-squares (t_local, t_merge, r2) for u(k) = t_local + t_merge/k.

    The model assumes the per-local-step compute cost is cadence-
    independent.  That holds in the merge-dominated regime (large
    n_vdpus — the paper's regime), but at small grids on CPU the
    cadence body (vmapped per-vDPU scan) can cost *more* per step than
    the merged body, making t_merge come out <= 0.  Rather than hide
    that behind a clamp, the fit is returned with its R² so callers can
    mark the cell invalid (`cadence_fit_valid` in the JSON)."""
    A = np.array([[1.0, 1.0 / k] for k in cadences])
    b = np.asarray(us_per_step)
    (t_local, t_merge), *_ = np.linalg.lstsq(A, b, rcond=None)
    pred = A @ np.array([t_local, t_merge])
    ss_res = float(np.sum((b - pred) ** 2))
    ss_tot = float(np.sum((b - b.mean()) ** 2))
    r2 = 1.0 - ss_res / ss_tot if ss_tot > 0 else 1.0
    valid = bool(t_merge > 0 and r2 > 0.8)
    return (max(float(t_local), 0.0), max(float(t_merge), 0.0),
            round(r2, 4), valid)


def throughput_sweep(vdpus, precisions, cadences, X, y, *,
                     timed_steps, warmup, iters, plan_vdpus=()):
    """linreg steps/s per (n_vdpus, precision, merge_every, pipeline,
    plan) cell, plus the per-cell merge-fraction from the cadence fit,
    the analytic wire bytes, and — for overlap cells — the share of the
    baseline merge the pipeline hid.  fp32 cells at grid sizes in
    ``plan_vdpus`` additionally sweep the composed merge plans
    (slowmo / topk / adaptive) at the baseline pipeline."""
    cells = []
    for v in vdpus:
        grid = make_cpu_grid(v)
        for prec in precisions:
            # build closures ONCE per (v, prec): stable compile-cache
            # keys, so timed fits measure steady-state step rate (the
            # quantized paths capture fresh scale arrays per build and
            # would otherwise retrace every call)
            data, n, local_fn, update_fn, w0 = make_linreg_step(
                grid, X, y, lr=0.05, precision=prec)
            # the pipeline axis is swept where the cadence fit is
            # meaningful: the fp32 dataset (int16/int8 cells are
            # interpret-mode-bound on CPU and carry fit_valid=false)
            pipelines = PIPELINES if prec == "fp32" else PIPELINES[:1]
            base_t_merge = None
            for pname, overlap, bits in pipelines:
                cfg = _compression(bits)
                per_k = {}
                for k in cadences:
                    us = time_fn(
                        lambda k=k: grid.fit(
                            init_state=w0, local_fn=local_fn,
                            update_fn=update_fn, data=data,
                            steps=timed_steps, merge_every=k,
                            overlap_merge=overlap,
                            merge_compression=cfg),
                        warmup=warmup, iters=iters)
                    per_k[k] = us / timed_steps      # us per local step
                t_local, t_merge, r2, valid = _fit_merge_model(
                    list(per_k), list(per_k.values()))
                if pname == "baseline":
                    base_t_merge = t_merge if valid else None
                # share of the baseline merge the pipeline hid.  Judged
                # against the *baseline* fit only: a fully-hidden merge
                # flattens u(k), which zeroes the overlap cell's own
                # t_merge and its r2 (nothing left to explain) — that is
                # the success case, not an unmeasurable one.
                hidden = 0.0
                if overlap and base_t_merge:
                    hidden = max(0.0,
                                 1.0 - max(t_merge, 0.0) / base_t_merge)
                for k, us_step in per_k.items():
                    wire = grid.merge_wire_spec(
                        local_fn, update_fn, w0, data, merge_every=k)
                    frac = (t_merge / k) / us_step if us_step > 0 else 0.0
                    cell = {
                        "algo": "linreg", "workload": "linreg",
                        "batch_size": "full", "mesh": "none",
                        "n_vdpus": v, "precision": prec,
                        "merge_every": k, "pipeline": pname,
                        "plan": "avg",
                        "us_per_step": round(us_step, 2),
                        "steps_per_s": round(1e6 / us_step, 1),
                        "merge_fraction": round(min(frac, 1.0), 4),
                        "merge_bytes": comp.wire_bytes(wire, cfg),
                        "merge_fraction_overlapped": round(hidden, 4),
                        "t_local_us_per_step": round(t_local, 2),
                        "t_merge_us_per_round": round(t_merge, 2),
                        "cadence_fit_r2": r2,
                        "cadence_fit_valid": valid,
                    }
                    cells.append(cell)
                    note = "" if valid else "  (fit invalid)"
                    print(f"linreg v={v:5d} {prec:5s} {pname:12s} "
                          f"k={k:2d}  "
                          f"{cell['steps_per_s']:9.1f} steps/s  "
                          f"merge {100 * cell['merge_fraction']:5.1f}%"
                          f"  wire {cell['merge_bytes']:5d}B{note}",
                          flush=True)
            if prec != "fp32" or v not in plan_vdpus:
                continue
            # ---- the composed-plan axis (baseline pipeline) ----
            for pname in PLANS:
                per_k = {}
                for k in cadences:
                    us = time_fn(
                        lambda k=k: grid.fit(
                            init_state=w0, local_fn=local_fn,
                            update_fn=update_fn, data=data,
                            steps=timed_steps,
                            merge_plan=_plan(pname, k)),
                        warmup=warmup, iters=iters)
                    per_k[k] = us / timed_steps
                t_local, t_merge, r2, valid = _fit_merge_model(
                    list(per_k), list(per_k.values()))
                # controller-driven plans (adaptive, auto) re-decide k
                # mid-fit, so the u(k) model does not apply to their
                # cells
                if pname in ("adaptive", "auto"):
                    valid = False
                for k, us_step in per_k.items():
                    # controller plans always run the state wire (the
                    # EF buffer must keep one shape while k changes),
                    # so their k=1 cells must be costed on the state
                    # tree, not the cadence-1 partials wire
                    wire_k = max(k, 2) if pname in ("adaptive", "auto") \
                        else k
                    wire = grid.merge_wire_spec(
                        local_fn, update_fn, w0, data,
                        merge_every=wire_k)
                    frac = (t_merge / k) / us_step \
                        if valid and us_step > 0 else 0.0
                    cell = {
                        "algo": "linreg", "workload": "linreg",
                        "batch_size": "full", "mesh": "none",
                        "n_vdpus": v,
                        "precision": prec, "merge_every": k,
                        "pipeline": "baseline", "plan": pname,
                        "us_per_step": round(us_step, 2),
                        "steps_per_s": round(1e6 / us_step, 1),
                        "merge_fraction": round(min(frac, 1.0), 4),
                        "merge_bytes": comp.wire_bytes(
                            wire, _plan(pname, k).compression),
                        "merge_fraction_overlapped": 0.0,
                        "t_local_us_per_step": round(t_local, 2),
                        "t_merge_us_per_round": round(t_merge, 2),
                        "cadence_fit_r2": r2,
                        "cadence_fit_valid": valid,
                    }
                    cells.append(cell)
                    note = "" if valid else "  (fit invalid)"
                    print(f"linreg v={v:5d} {prec:5s} plan:{pname:9s}"
                          f"k={k:2d}  "
                          f"{cell['steps_per_s']:9.1f} steps/s  "
                          f"wire {cell['merge_bytes']:5d}B{note}",
                          flush=True)
    return cells


def mesh_sweep(mesh_vdpus, X, y, *, timed_steps, warmup, iters):
    """v6: linreg fp32 cells on the REAL mesh engine (shard_map over
    ``make_mesh_grid``) at baseline and int8 pipelines.  Returns
    ``(cells, mesh_labels)`` — the labels (e.g. ``["2x4"]``) land in
    ``config.mesh_grids`` so the completeness promise matches exactly
    what the runtime could generate (empty on a single device)."""
    cells, labels = [], []
    for v in mesh_vdpus:
        grid = _mesh_grid_or_none(v)
        if grid is None:
            print(f"mesh v={v}: skipped (need >1 device and "
                  f"divisibility)", flush=True)
            continue
        label = _mesh_label(grid)
        if label not in labels:
            labels.append(label)
        data, n, local_fn, update_fn, w0 = make_linreg_step(
            grid, X, y, lr=0.05)
        for pname, overlap, bits in PIPELINES:
            if pname not in MESH_PIPELINES:
                continue
            cfg = _compression(bits)
            per_k = {}
            for k in CADENCES:
                us = time_fn(
                    lambda k=k: grid.fit(
                        init_state=w0, local_fn=local_fn,
                        update_fn=update_fn, data=data,
                        steps=timed_steps, merge_every=k,
                        overlap_merge=overlap, merge_compression=cfg),
                    warmup=warmup, iters=iters)
                per_k[k] = us / timed_steps
            t_local, t_merge, r2, valid = _fit_merge_model(
                list(per_k), list(per_k.values()))
            for k, us_step in per_k.items():
                wire = grid.merge_wire_spec(
                    local_fn, update_fn, w0, data, merge_every=k)
                frac = (t_merge / k) / us_step if us_step > 0 else 0.0
                cell = {
                    "algo": "linreg", "workload": "linreg",
                    "batch_size": "full", "mesh": label,
                    "n_vdpus": v, "precision": "fp32",
                    "merge_every": k, "pipeline": pname,
                    "plan": "avg",
                    "us_per_step": round(us_step, 2),
                    "steps_per_s": round(1e6 / us_step, 1),
                    "merge_fraction": round(min(frac, 1.0), 4),
                    "merge_bytes": comp.wire_bytes(wire, cfg),
                    "merge_fraction_overlapped": 0.0,
                    "t_local_us_per_step": round(t_local, 2),
                    "t_merge_us_per_round": round(t_merge, 2),
                    "cadence_fit_r2": r2,
                    "cadence_fit_valid": valid,
                }
                cells.append(cell)
                print(f"linreg v={v:5d} fp32  mesh:{label:6s} "
                      f"{pname:8s} k={k:2d}  "
                      f"{cell['steps_per_s']:9.1f} steps/s  "
                      f"wire {cell['merge_bytes']:5d}B", flush=True)
    return cells, labels


def weak_scaling_sweep(weak_vdpus, key, *, timed_steps, warmup, iters):
    """v6: weak scaling — the grid grows, each vDPU keeps
    ``WEAK_ROWS_PER_VDPU`` resident rows (the paper's protocol; strong
    scaling shrinks the partition instead).  Rows record both the
    emulated-grid run and, when the runtime has devices for it, the
    mesh run of the same shape.  The headline column is ``rows_per_s``:
    with a perfectly amortised merge it grows linearly with the grid."""
    rows_out = []
    for v in weak_vdpus:
        n_rows = v * WEAK_ROWS_PER_VDPU
        X, y, _ = datasets.regression(key, n_rows, WEAK_FEATURES)
        grids = [make_cpu_grid(v)]
        mesh_grid = _mesh_grid_or_none(v)
        if mesh_grid is not None:
            grids.append(mesh_grid)
        for grid in grids:
            label = _mesh_label(grid)
            data, n, local_fn, update_fn, w0 = make_linreg_step(
                grid, X, y, lr=0.05)
            us = time_fn(
                lambda: grid.fit(
                    init_state=w0, local_fn=local_fn,
                    update_fn=update_fn, data=data, steps=timed_steps,
                    merge_every=WEAK_MERGE_EVERY),
                warmup=warmup, iters=iters)
            us_step = us / timed_steps
            row = {
                "workload": "linreg", "mesh": label,
                "n_vdpus": v, "rows_per_vdpu": WEAK_ROWS_PER_VDPU,
                "rows": n_rows, "features": WEAK_FEATURES,
                "precision": "fp32",
                "merge_every": WEAK_MERGE_EVERY,
                "us_per_step": round(us_step, 2),
                "steps_per_s": round(1e6 / us_step, 1),
                "rows_per_s": round(n_rows * 1e6 / us_step, 1),
            }
            rows_out.append(row)
            print(f"weak v={v:6d} rows={n_rows:7d} mesh:{label:6s} "
                  f"{row['steps_per_s']:9.1f} steps/s  "
                  f"{row['rows_per_s']:.3g} rows/s", flush=True)
    return rows_out


def _bind_workload(name, grid, key, *, rows, features):
    """One bound Program per (workload, grid) — stable compile-cache
    keys across the timed cadence/batch sweep, like make_linreg_step
    for the base cells.  The estimator comes from the config's one
    name -> workload mapping (``PimMLConfig.workload_spec``); only the
    dataset choice is benchmark-local."""
    import dataclasses as _dc

    from repro.configs.pim_ml import CONFIG

    # the linreg base-cell hyperparameters (lr=0.05) are what the
    # config's builder uses, so workload cells stay comparable
    wl = _dc.replace(CONFIG, workload=name).workload_spec()
    if name == "linreg":
        X, y, _ = datasets.regression(key, rows, features)
    elif name == "svm":
        X, y, _ = datasets.binary_classification(key, rows, features)
    elif name == "multinomial":
        X, y = datasets.mixture_classification(key, rows, features,
                                               n_classes=CONFIG.mn_classes)
    else:
        raise ValueError(name)
    return wl.bind(grid, X, y), (X, y)


def workload_sweep(vdpus, key, *, rows, features, timed_steps, warmup,
                   iters):
    """The v4 Workload-protocol cells: steps/s per (workload, n_vdpus,
    merge_every, batch_size), fp32 at the baseline pipeline / default
    plan, all through the one generic ``api.fit`` path.  ``linreg``
    appears only at ``batch_size != "full"`` (its full-batch cells are
    the base sweep); the minibatch cells are the acceptance row — a
    ``batch_size < rows_per_vdpu`` cell must beat its full-batch
    sibling in steps/s (the sampled fraction is all the local compute
    a step pays)."""
    cells = []
    for v in vdpus:
        grid = make_cpu_grid(v)
        per = -(-rows // v)
        for wname in WORKLOADS:
            program, _ = _bind_workload(wname, grid, key, rows=rows,
                                        features=features)
            for bs_label in BATCH_SIZES:
                if wname == "linreg" and bs_label == "full":
                    continue          # base cells cover linreg full-batch
                bs = None if bs_label == "full" else min(bs_label, per)
                for k in WORKLOAD_CADENCES:
                    us = time_fn(
                        lambda k=k, bs=bs: program.fit(
                            steps=timed_steps, merge_every=k,
                            batch_size=bs),
                        warmup=warmup, iters=iters)
                    us_step = us / timed_steps
                    cell = {
                        "algo": wname, "workload": wname,
                        "batch_size": bs_label, "mesh": "none",
                        "n_vdpus": v, "precision": "fp32",
                        "merge_every": k, "pipeline": "baseline",
                        "plan": "avg",
                        "us_per_step": round(us_step, 2),
                        "steps_per_s": round(1e6 / us_step, 1),
                    }
                    cells.append(cell)
                    print(f"{wname:11s} v={v:5d} fp32  batch="
                          f"{str(bs_label):5s} k={k:2d}  "
                          f"{cell['steps_per_s']:9.1f} steps/s",
                          flush=True)
    return cells


def workload_accuracy_sweep(v, key, *, rows, features, steps):
    """Accuracy-vs-workload: SVM and multinomial logreg under
    MergePlan cadence {1, 4} x batch {full, minibatch} — the new
    estimators must stay oracle-matching (tests pin the numpy-oracle
    parity; this records the curves next to the throughput cells).
    ``oracle_accuracy`` is the exact full-batch cadence-1 run of the
    same estimator."""
    curves = []
    grid = make_cpu_grid(v)
    per = -(-rows // v)
    accuracy_fn = {"svm": svm_accuracy,
                   "multinomial": multinomial_accuracy}
    for wname in ("svm", "multinomial"):
        program, (X, y) = _bind_workload(wname, grid, key, rows=rows,
                                         features=features)
        # the sweep's first cell (batch="full", k=1) IS the exact
        # full-batch run — it doubles as the oracle row, so no
        # redundant training pass
        oracle = None
        for bs_label in BATCH_SIZES:
            bs = None if bs_label == "full" else min(bs_label, per)
            for k in WORKLOAD_CADENCES:
                res = program.fit(steps=steps, merge_every=k,
                                  batch_size=bs)
                acc = accuracy_fn[wname](res.state, X, y)
                if oracle is None:
                    assert bs is None and k == 1
                    oracle = acc
                entry = {
                    "workload": wname, "n_vdpus": v,
                    "merge_every": k, "batch_size": bs_label,
                    "steps": steps, "accuracy": acc,
                    "oracle_accuracy": oracle,
                }
                curves.append(entry)
                print(f"workload-accuracy {wname:11s} k={k} "
                      f"batch={str(bs_label):5s} acc={acc:.4f} "
                      f"(oracle {oracle:.4f})", flush=True)
    return curves


def accuracy_sweep(v, cadences, key, *, rows, features, steps):
    """Accuracy-vs-cadence at fixed grid size (fp32): does amortising
    the merge cost convergence?  linreg reports distance to the
    closed-form solution; logreg reports classification accuracy."""
    curves = []
    Xr, yr, _ = datasets.regression(key, rows, features)
    w_star = closed_form(Xr, yr)
    Xc, yc, _ = datasets.binary_classification(key, rows, features)
    grid = make_cpu_grid(v)
    for k in cadences:
        lin = train_linreg(grid, Xr, yr, lr=0.05, steps=steps,
                           merge_every=k)
        log = train_logreg(grid, Xc, yc, lr=0.5, steps=steps,
                           merge_every=k)
        entry = {
            "n_vdpus": v, "merge_every": k, "steps": steps,
            "linreg_final_loss": float(lin.history[-1]["loss"]),
            "linreg_w_err": float(
                np.linalg.norm(np.asarray(lin.w - w_star))),
            "logreg_final_loss": float(log.history[-1]["loss"]),
            "logreg_accuracy": accuracy(log.w, Xc, yc),
        }
        curves.append(entry)
        print(f"accuracy v={v} k={k:2d}  linreg_w_err="
              f"{entry['linreg_w_err']:.4f}  "
              f"logreg_acc={entry['logreg_accuracy']:.4f}", flush=True)
    return curves


def pipeline_accuracy_sweep(v, key, *, rows, features, steps,
                            merge_every):
    """Does shrinking/hiding the merge cost convergence?  One linreg +
    logreg run per pipeline at fixed grid/cadence: the int8 wire must
    stay within error-feedback tolerance of exact, overlap within
    staleness tolerance."""
    curves = []
    Xr, yr, _ = datasets.regression(key, rows, features)
    w_star = closed_form(Xr, yr)
    Xc, yc, _ = datasets.binary_classification(key, rows, features)
    grid = make_cpu_grid(v)
    for pname, overlap, bits in PIPELINES:
        cfg = _compression(bits)
        lin = train_linreg(grid, Xr, yr, lr=0.05, steps=steps,
                           merge_every=merge_every,
                           overlap_merge=overlap, merge_compression=cfg)
        log = train_logreg(grid, Xc, yc, lr=0.5, steps=steps,
                           merge_every=merge_every,
                           overlap_merge=overlap, merge_compression=cfg)
        entry = {
            "n_vdpus": v, "merge_every": merge_every, "steps": steps,
            "pipeline": pname,
            "linreg_w_err": float(
                np.linalg.norm(np.asarray(lin.w - w_star))),
            "logreg_accuracy": accuracy(log.w, Xc, yc),
        }
        curves.append(entry)
        print(f"pipeline-accuracy {pname:12s}  linreg_w_err="
              f"{entry['linreg_w_err']:.4f}  "
              f"logreg_acc={entry['logreg_accuracy']:.4f}", flush=True)
    return curves


def plan_accuracy_sweep(v, key, *, rows, features, steps, merge_every):
    """Accuracy-vs-plan at fixed grid/cadence, with the analytic wire
    bytes per merge round beside each row: the acceptance question is
    whether top-k lands *below the int8 row's bytes at comparable
    accuracy* (error feedback carries the dropped mass), and whether
    SlowMo / adaptive cadence stay within convergence tolerance."""
    curves = []
    Xr, yr, _ = datasets.regression(key, rows, features)
    w_star = closed_form(Xr, yr)
    Xc, yc, _ = datasets.binary_classification(key, rows, features)
    grid = make_cpu_grid(v)
    for pname in ("avg", "int8") + PLANS:
        plan = _plan(pname, merge_every)
        lin = train_linreg(grid, Xr, yr, lr=0.05, steps=steps,
                           merge_plan=plan)
        log = train_logreg(grid, Xc, yc, lr=0.5, steps=steps,
                           merge_plan=plan)
        data, n, lf, uf, w0 = make_linreg_step(grid, Xr, yr, lr=0.05)
        wire = grid.merge_wire_spec(lf, uf, w0, data,
                                    merge_every=merge_every)
        entry = {
            "n_vdpus": v, "merge_every": merge_every, "steps": steps,
            "plan": pname,
            "merge_bytes": comp.wire_bytes(wire, plan.compression),
            "linreg_w_err": float(
                np.linalg.norm(np.asarray(lin.w - w_star))),
            "logreg_accuracy": accuracy(log.w, Xc, yc),
        }
        curves.append(entry)
        print(f"plan-accuracy {pname:9s}  wire {entry['merge_bytes']:5d}B"
              f"  linreg_w_err={entry['linreg_w_err']:.4f}  "
              f"logreg_acc={entry['logreg_accuracy']:.4f}", flush=True)
    return curves


def run(*, smoke: bool = False, out: str = "BENCH_scaling.json"):
    key = jax.random.PRNGKey(0)
    vdpus = VDPUS_SMOKE if smoke else VDPUS_FULL
    rows = 2048 if smoke else 16384
    features = 16 if smoke else 32
    timed_steps = 16                       # divisible by every cadence
    warmup, iters = (1, 2) if smoke else (1, 3)

    plan_vdpus = vdpus if smoke else PLAN_VDPUS_FULL

    workload_vdpus = (vdpus[-1:] if smoke else WORKLOAD_VDPUS_FULL)

    X, y, _ = datasets.regression(key, rows, features)
    cells = throughput_sweep(vdpus, PRECISIONS, CADENCES, X, y,
                             timed_steps=timed_steps, warmup=warmup,
                             iters=iters, plan_vdpus=plan_vdpus)
    cells += workload_sweep(workload_vdpus, key, rows=rows,
                            features=features, timed_steps=timed_steps,
                            warmup=warmup, iters=iters)
    mesh_vdpus = MESH_VDPUS_SMOKE if smoke else MESH_VDPUS_FULL
    mesh_cells, mesh_labels = mesh_sweep(
        mesh_vdpus, X, y, timed_steps=timed_steps, warmup=warmup,
        iters=iters)
    cells += mesh_cells
    weak_vdpus = WEAK_VDPUS_SMOKE if smoke else WEAK_VDPUS_FULL
    weak_rows = weak_scaling_sweep(
        weak_vdpus, key, timed_steps=timed_steps, warmup=warmup,
        iters=max(1, iters - 1))
    acc_v = 16 if smoke else 64
    acc_steps = 60 if smoke else 200
    curves = accuracy_sweep(acc_v, CADENCES, key,
                            rows=rows, features=features,
                            steps=acc_steps)
    pipe_curves = pipeline_accuracy_sweep(
        acc_v, key, rows=rows, features=features, steps=acc_steps,
        merge_every=4)
    plan_curves = plan_accuracy_sweep(
        acc_v, key, rows=rows, features=features, steps=acc_steps,
        merge_every=4)
    workload_curves = workload_accuracy_sweep(
        acc_v, key, rows=rows, features=features, steps=acc_steps)

    result = {
        "schema": "bench_scaling/v6",
        "config": {
            "backend": jax.default_backend(),
            # splitting one CPU into N host devices changes absolute
            # throughput (the emulated cells lose threads) — device
            # topology is part of regression comparability
            "n_devices": len(jax.devices()),
            "smoke": smoke,
            "rows": rows, "features": features,
            "timed_steps": timed_steps,
            "n_vdpus": list(vdpus),
            "merge_every": list(CADENCES),
            "precisions": list(PRECISIONS),
            "pipelines": [p[0] for p in PIPELINES],
            "pipeline_precisions": ["fp32"],
            "plans": list(PLANS),
            "plan_n_vdpus": list(plan_vdpus),
            "plan_precisions": ["fp32"],
            "topk_frac": TOPK_FRAC,
            "workloads": list(WORKLOADS),
            "workload_n_vdpus": list(workload_vdpus),
            "workload_merge_every": list(WORKLOAD_CADENCES),
            "batch_sizes": list(BATCH_SIZES),
            "accuracy_n_vdpus": acc_v, "accuracy_steps": acc_steps,
            # v6: mesh_grids holds the labels the runtime could
            # actually build ([] on one device — the promise adapts)
            "mesh_grids": mesh_labels,
            "mesh_n_vdpus": [v for v in mesh_vdpus
                             if _mesh_grid_or_none(v) is not None],
            "mesh_pipelines": list(MESH_PIPELINES),
            "weak_n_vdpus": list(weak_vdpus),
            "weak_rows_per_vdpu": WEAK_ROWS_PER_VDPU,
            "weak_merge_every": WEAK_MERGE_EVERY,
        },
        "throughput": cells,
        "weak_scaling": weak_rows,
        "accuracy_vs_cadence": curves,
        "accuracy_vs_pipeline": pipe_curves,
        "accuracy_vs_plan": plan_curves,
        "accuracy_vs_workload": workload_curves,
    }
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {os.path.abspath(out)} "
          f"({len(cells)} throughput cells, {len(weak_rows)} weak-"
          f"scaling rows, {len(curves)} accuracy rows, "
          f"{len(pipe_curves)} pipeline rows, {len(plan_curves)} plan "
          f"rows, {len(workload_curves)} workload rows)",
          flush=True)
    return result


if __name__ == "__main__":
    place_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-size sweep (n_vdpus <= 16, small dataset)")
    ap.add_argument("--out", default="BENCH_scaling.json")
    args = ap.parse_args()
    run(smoke=args.smoke, out=args.out)
