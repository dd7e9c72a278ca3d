"""Paper Table: per-algorithm training throughput on the PIM grid vs the
processor-centric ("CPU direct") formulation, all numeric variants —
plus the step-engine table: compiled lax.scan fit vs the seed's
one-dispatch-per-step Python loop (steps/sec, the host-bottleneck number
the paper's I5 is about).

CSV columns: name, us_per_iteration, derived (rows/s | steps/s | note).
"""

import jax
import jax.numpy as jnp

from benchmarks.common import time_fn, emit
from repro.core import datasets, make_cpu_grid
from repro.core.mlalgos import (train_linreg, train_logreg, train_kmeans,
                                train_dtree, train_svm,
                                train_multinomial, LinReg)
from repro.configs.pim_ml import CONFIG as C
from repro.compile_cache import place_compile_cache


def _one_step_timer(build_step, *args):
    """Time one jitted PIM iteration."""
    step, state, data = build_step(*args)
    return time_fn(lambda: step(state, data)[0])


def bench_step_engines(grid, X, y, Xk, steps: int = 50):
    """steps/sec: compiled scan engine vs the per-step Python loop.

    This measures the host-dispatch bottleneck the paper's I5 is about,
    so it runs at per-step-compute scales where the host matters (8K
    rows; at 32K+ rows the step is compute-bound on this CPU and both
    engines converge).  The scan numbers are steady-state (warmup
    populates the grid's signature-keyed compile cache; timed calls
    reuse it).  The Python loop re-jits per call — exactly the seed's
    behaviour being replaced.  The int8/int16 paths are excluded: their
    closures capture freshly quantized datasets each call, so every
    timed call would measure interpret-kernel recompilation, not step
    rate.
    """
    Xe, ye, Xke = X[:8192], y[:8192], Xk[:8192]
    us_scan = time_fn(lambda: train_linreg(grid, Xe, ye, lr=0.05,
                                           steps=steps),
                      warmup=1, iters=3)
    us_py = time_fn(lambda: train_linreg(grid, Xe, ye, lr=0.05,
                                         steps=steps, engine="python"),
                    warmup=1, iters=3)
    emit(f"linreg_fp32_scan_engine_{steps}steps", us_scan,
         f"{steps * 1e6 / us_scan:.0f} steps/s")
    emit(f"linreg_fp32_python_loop_{steps}steps", us_py,
         f"{steps * 1e6 / us_py:.0f} steps/s "
         f"(scan {us_py / us_scan:.1f}x faster)")

    # the merge-cadence row (config-driven): k local steps per host
    # merge amortises the paper's host-communication term
    if C.merge_every > 1:
        us_cad = time_fn(lambda: train_linreg(grid, Xe, ye, lr=0.05,
                                              steps=steps,
                                              merge_every=C.merge_every),
                         warmup=1, iters=3)
        emit(f"linreg_fp32_scan_cadence{C.merge_every}_{steps}steps",
             us_cad, f"{steps * 1e6 / us_cad:.0f} steps/s "
             f"(1 merge per {C.merge_every} steps)")

    # the minibatch row (Workload-protocol axis): sample 1/4 of each
    # vDPU's resident rows per local step — the steps/s win PIM-Opt's
    # minibatch local SGD banks.  One bound program keeps the timed
    # fits on stable compile-cache keys.
    per = -(-Xe.shape[0] // grid.n_vdpus)
    program = LinReg(lr=0.05).bind(grid, Xe, ye)
    us_mini = time_fn(lambda: program.fit(steps=steps,
                                          batch_size=max(1, per // 4)),
                      warmup=1, iters=3)
    emit(f"linreg_fp32_scan_minibatch{max(1, per // 4)}_{steps}steps",
         us_mini, f"{steps * 1e6 / us_mini:.0f} steps/s "
         f"(batch {max(1, per // 4)}/{per} rows per vDPU)")

    # the merge-pipeline row (config-driven): overlap and/or compress
    # the merge itself (see PimGrid.fit / configs.pim_ml)
    if C.overlap_merge or C.merge_compression_bits:
        from repro.distributed.compression import CompressionConfig
        cmp = (CompressionConfig(bits=C.merge_compression_bits)
               if C.merge_compression_bits else None)
        us_pipe = time_fn(
            lambda: train_linreg(grid, Xe, ye, lr=0.05, steps=steps,
                                 merge_every=C.merge_every,
                                 overlap_merge=C.overlap_merge,
                                 merge_compression=cmp),
            warmup=1, iters=3)
        tag = "+".join([s for s, on in (
            ("overlap", C.overlap_merge),
            (f"efq{C.merge_compression_bits}",
             C.merge_compression_bits)) if on])
        emit(f"linreg_fp32_scan_{tag}_{steps}steps", us_pipe,
             f"{steps * 1e6 / us_pipe:.0f} steps/s "
             f"(merge pipeline: {tag})")

    us_scan = time_fn(lambda: train_kmeans(grid, Xke, C.km_clusters,
                                           iters=steps),
                      warmup=1, iters=3)
    us_py = time_fn(lambda: train_kmeans(grid, Xke, C.km_clusters,
                                         iters=steps, engine="python"),
                    warmup=1, iters=3)
    emit(f"kmeans_fp32_scan_engine_{steps}steps", us_scan,
         f"{steps * 1e6 / us_scan:.0f} steps/s")
    emit(f"kmeans_fp32_python_loop_{steps}steps", us_py,
         f"{steps * 1e6 / us_py:.0f} steps/s "
         f"(scan {us_py / us_scan:.1f}x faster)")


def run():
    key = jax.random.PRNGKey(0)
    grid = make_cpu_grid(C.n_vdpus)
    rows = min(C.reg_rows, 32768)            # CPU-container scale
    X, y, _ = datasets.regression(key, rows, C.reg_features)

    # --- linear regression: PIM grid (fp32/int16/int8) vs direct jnp ---
    for prec in ("fp32", "int16", "int8"):
        def once(prec=prec):
            return train_linreg(grid, X, y, lr=0.05, steps=1,
                                precision=prec)
        us = time_fn(once, warmup=1, iters=3)
        emit(f"linreg_pim_{prec}_iter", us, f"{rows * 1e6 / us:.0f} rows/s")

    Xd, yd = jnp.asarray(X), jnp.asarray(y)

    @jax.jit
    def direct_step(w):
        return w - 0.05 * Xd.T @ (Xd @ w - yd) / rows

    emit("linreg_cpu_direct_iter",
         time_fn(direct_step, jnp.zeros((C.reg_features,))), "baseline")

    # --- logistic regression: sigmoid variants ---
    Xc, yc, _ = datasets.binary_classification(key, rows, C.reg_features)
    for sig in ("exact", "lut", "taylor"):
        def once(sig=sig):
            return train_logreg(grid, Xc, yc, lr=0.5, steps=1, sigmoid=sig)
        emit(f"logreg_pim_{sig}_iter", time_fn(once, warmup=1, iters=3),
             "")

    # --- linear SVM + multinomial logreg (Workload plugins, PIM-Opt's
    # second workload and the C-class generalisation) ---
    for prec in ("fp32", "int8"):
        def once_svm(prec=prec):
            return train_svm(grid, Xc, yc, lr=0.1, steps=1,
                             precision=prec)
        emit(f"svm_pim_{prec}_iter", time_fn(once_svm, warmup=1,
                                             iters=3), "hinge")
    Xm, ym = datasets.mixture_classification(key, rows, C.reg_features,
                                             C.mn_classes)
    for sm in ("exact", "lut"):
        def once_mn(sm=sm):
            return train_multinomial(grid, Xm, ym,
                                     n_classes=C.mn_classes, lr=0.5,
                                     steps=1, softmax=sm)
        emit(f"multinomial_pim_{sm}_iter",
             time_fn(once_mn, warmup=1, iters=3),
             f"C={C.mn_classes}")

    # --- K-means ---
    Xk, _, _ = datasets.blobs(key, min(C.km_rows, 32768), C.km_features,
                              C.km_clusters)
    for prec in ("fp32", "int16"):
        def once(prec=prec):
            return train_kmeans(grid, Xk, C.km_clusters, iters=1,
                                precision=prec)
        emit(f"kmeans_pim_{prec}_iter", time_fn(once, warmup=1, iters=3),
             f"k={C.km_clusters}")

    # --- decision tree (full build; levels are the unit of work) ---
    Xt, yt = datasets.mixture_classification(
        key, min(C.dt_rows, 16384), C.dt_features, C.dt_classes)

    def tree_once():
        return train_dtree(grid, Xt, yt, max_depth=C.dt_depth,
                           n_bins=C.dt_bins, n_classes=C.dt_classes)
    emit("dtree_pim_full_build", time_fn(tree_once, warmup=1, iters=2),
         f"depth={C.dt_depth}")

    # --- step engine: compiled scan vs per-step Python loop ---
    bench_step_engines(grid, X, y, Xk)


if __name__ == "__main__":
    place_compile_cache()
    run()
