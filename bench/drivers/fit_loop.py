"""Traffic of whole fits: a user trains a model from raw arrays that are
already resident on the chips, again and again.

Set-up makes the rows on the device from the run's seed, builds the grid and the estimator, and runs one
warm-up fit that compiles every program the window runs.  The window
then calls the user's entry point, ``repro.core.mlalgos.api.fit``, back
to back on the same raw arrays until ``--seconds`` have passed; it
closes when the first fit that ends after the deadline ends.

End-to-end metrics: ``setup_s``, from the start of the process to the
start of the window, and ``fit_s``, the window's length over the fits
completed in it.
"""

from __future__ import annotations

import sys
import time
import traceback


def grid_and_rows(cfg: dict, devices):
    """The estimator's grid and where the raw rows live.

    A configuration with a ``mesh`` (``{"pods": p}``) spreads its vDPUs
    over the cell's chips as a ``(pods, chips / pods)`` mesh
    (``make_mesh_grid``), and its rows are sharded over the same axes so
    that each chip makes and holds its own; without one, the vDPUs and
    the rows sit on the first chip."""
    import numpy as np
    from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec,
                              SingleDeviceSharding)

    from repro.core import make_cpu_grid, make_mesh_grid

    if "mesh" not in cfg:
        return make_cpu_grid(cfg["n_vdpus"]), SingleDeviceSharding(devices[0])
    pods = cfg["mesh"]["pods"]
    axes = ("pod", "data")
    mesh = Mesh(np.asarray(devices).reshape(pods, len(devices) // pods),
                axes, axis_types=(AxisType.Auto,) * 2)
    grid = make_mesh_grid(cfg["n_vdpus"], mesh=mesh)
    return grid, NamedSharding(mesh, PartitionSpec(axes))


def run(ctx) -> dict:
    import jax

    from bench.harness import FIT, WINDOW, profiled
    from repro.core.mlalgos import api

    cfg, traffic, algo = ctx.cfg, ctx.traffic, ctx.algo
    grid, rows = grid_and_rows(cfg, ctx.devices)
    X, y = algo.generate(cfg, ctx.key, rows)
    jax.block_until_ready((X, y))
    est = ctx.make_estimator(cfg)
    kw = algo.fit_kwargs(traffic, ctx.seed)

    def fit():
        res = api.fit(est, grid, X, y, **kw)
        jax.block_until_ready((res.state, res.history))
        return res

    # one whole fit, as the window runs it: a fit whose programs carry
    # constants of this seed's data or sample schedule compiles them
    # here, whether or not another run of the same seed cached them
    fit()
    setup_s = time.perf_counter() - ctx.t_start

    results, attempted = [], 0
    with profiled(ctx.trace_dir), jax.profiler.TraceAnnotation(WINDOW):
        wall0 = time.time()
        t0 = time.perf_counter()
        deadline = t0 + ctx.seconds
        while True:
            attempted += 1
            with jax.profiler.TraceAnnotation(FIT):
                try:
                    results.append(fit())
                except Exception:  # a fit that fails counts as failed
                    traceback.print_exc(file=sys.stderr)
            t1 = time.perf_counter()
            if t1 >= deadline:
                break
        wall1 = time.time()
    window_s = t1 - t0
    e2e = {"setup_s": setup_s}
    if results:
        e2e["fit_s"] = window_s / len(results)
    return {"attempted": attempted, "completed": len(results),
            "results": results, "X": X, "y": y, "grid": grid,
            "window_wall": (wall0, wall1),
            "steps_per_fit": kw["steps"], "end_to_end": e2e}
