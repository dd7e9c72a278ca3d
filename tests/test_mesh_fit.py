"""The four-chip deployment ``logreg-int8-16m-x4`` at a size the CPU holds.

``LogReg(precision="int8", sigmoid="lut")`` trains through ``api.fit``
on a ``make_mesh_grid`` grid over a ``(1, 4)`` mesh, as the deployment
runs on four chips: the rows sharded so that each device holds its own
vDPUs, the int8 kernels and the LUT kernel under ``shard_map``, one
all-reduce of the partials a step.  8 vDPUs over 1,948 rows leave the
last vDPUs with padding rows.

This process sees one CPU device, so each case runs in a child process
over four forced host devices (``python tests/test_mesh_fit.py
<case>``), which prints one JSON line.  Each case checks that the mesh
fit

* equals the one-device grid's fit (``make_cpu_grid``) within
  :data:`MESH_TOL`;
* is within the limits of its four-chip cell
  (``bench/limits/logreg-int8.gd.x4.json``) of the benchmark's plain
  reference (``bench/algos/logreg.py``, which imports nothing of the
  program);

and that the reference with the exchange between chips left out fails
those limits.  A second mesh fit, on other rows and so with other
quantization scales, finds its runner in the grid's cache and still
equals the one-device grid's fit.
"""

import functools
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = "bench/configs/logreg-int8-16m-x4.json"
LIMITS = "bench/limits/logreg-int8.gd.x4.json"
SEED = 2 ** 31 + 101
DEVICES = 4
SMALL = {"n_vdpus": 8, "data": {"rows": 1948}}
CASES = {"gd": {"steps": 6, "batch_size": None},
         "sgd": {"steps": 20, "batch_size": 16}}
# The mesh sums each device's partials and then sums across devices;
# the one-device grid sums all vDPUs at once.  The float32 order
# differs, and a 16-bit requantization of the weights can then round
# the other way on its last bit, which the next steps carry on (one
# such flip puts a fit 8.5e-06 from the reference).  Over six seeds the
# two grids' fits were at most 1.2e-07 apart in weight and 2.7e-06 in
# a step's loss; the reference without the exchange is 0.50-0.74 off.
MESH_TOL = 5e-05
CHILD_TIMEOUT_S = 300


def _child(case: str) -> dict:
    """One case's fits, run over four host devices; the numbers the
    test compares."""
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    import numpy as np

    from bench import harness
    from bench.algos import logreg as algo
    from bench.drivers.fit_loop import grid_and_rows
    from repro.core import make_cpu_grid
    from repro.core.mlalgos import api

    cfg = harness.load_json(os.path.join(ROOT, CONFIG))
    cfg["n_vdpus"] = SMALL["n_vdpus"]
    cfg["data"] = {**cfg["data"], **SMALL["data"]}
    traffic = CASES[case]
    devices = jax.devices()
    assert len(devices) == cfg["chips"] == DEVICES, devices

    grid, rows = grid_and_rows(cfg, devices)
    kw = algo.fit_kwargs(traffic, SEED)
    est = harness.make_estimator(cfg)

    def fits(X, y):
        """The mesh fit, the one-device fit, and the runners the mesh
        fit added to the grid's cache."""
        n = len(grid._fit_cache)
        mesh = algo.answer(api.fit(est, grid, X, y, **kw))
        one = algo.answer(api.fit(est, make_cpu_grid(cfg["n_vdpus"]),
                                  jax.device_put(X, devices[0]),
                                  jax.device_put(y, devices[0]), **kw))
        return mesh, one, len(grid._fit_cache) - n

    X, y = algo.generate(cfg, harness.seed_key(SEED), rows)
    mesh, one, _ = fits(X, y)
    X2, y2 = algo.generate(cfg, harness.seed_key(SEED + 1), rows)
    mesh2, one2, builds2 = fits(X2, y2)
    X, y = np.asarray(X), np.asarray(y)
    ref = algo.reference(cfg, traffic, X, y, SEED)
    no_exchange = algo.reference(cfg, traffic, X, y, SEED,
                                 no_exchange=True)
    return {"mesh_vs_one": algo.compare(mesh, one),
            "program": algo.compare(mesh, ref),
            "no_exchange": algo.compare(no_exchange, ref),
            "second_mesh_vs_one": algo.compare(mesh2, one2),
            "second_runner_builds": builds2}


@functools.cache
def _run_child(case: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={DEVICES}")
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), case], env=env,
        cwd=ROOT, capture_output=True, text=True, timeout=CHILD_TIMEOUT_S)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _within(nums: dict, limits: dict) -> bool:
    return all(nums[k] <= limits[k] for k in limits)


@pytest.mark.parametrize("case", sorted(CASES))
def test_mesh_fit_matches_one_device_and_reference(case):
    with open(os.path.join(ROOT, LIMITS)) as f:
        limits = json.load(f)["limits"]
    out = _run_child(case)
    assert out["mesh_vs_one"]["w_gap"] <= MESH_TOL, out
    assert out["mesh_vs_one"]["loss_gap"] <= MESH_TOL, out
    assert _within(out["program"], limits), out
    assert not _within(out["no_exchange"], limits), out


@pytest.mark.parametrize("case", sorted(CASES))
def test_second_mesh_fit_reuses_runner(case):
    out = _run_child(case)
    assert out["second_runner_builds"] == 0, out
    assert out["second_mesh_vs_one"]["w_gap"] <= MESH_TOL, out
    assert out["second_mesh_vs_one"]["loss_gap"] <= MESH_TOL, out


if __name__ == "__main__":
    print(json.dumps(_child(sys.argv[1])), flush=True)
