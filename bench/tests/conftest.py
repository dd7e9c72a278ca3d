"""Test support: a copy of the benchmark at sizes the CPU holds.

The tests run on the CPU, Pallas kernels in interpret mode, over four
host devices so that the four-chip cell runs its mesh:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# before JAX is first imported: four CPU devices stand for four chips
os.environ["XLA_FLAGS"] = " ".join(filter(None, [
    os.environ.get("XLA_FLAGS"),
    "--xla_force_host_platform_device_count=4"]))

import pytest  # noqa: E402

# every size the CPU can hold; rows leave some vDPUs with padding rows
SMALL = {
    "logreg-int8-16m": {"n_vdpus": 8,
                        "data": {"rows": 1948}},
    "kmeans-fp32-8m": {"n_vdpus": 8,
                       "data": {"rows": 1948}},
    "logreg-int8-16m-x4": {"n_vdpus": 8,
                           "data": {"rows": 1948}},
}
SMALL_TRAFFIC = {"gd100": {"steps": 6}, "lloyd10": {"steps": 4},
                 "sgd-b64": {"steps": 20, "batch_size": 16}}


def _merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) else v
    return out


# the four-chip cell, whose files are in ``bench/`` and whose entries
# wait for its readings on four chips (PERF.md): added here as new
# entries, as a later change would add them
MESH_CELL = "logreg-int8.gd.x4"
MESH_METRICS = ("compile_ms_per_fit", "step_ms", "step_mfu",
                "fxp_matmul_roofline", "device_idle_share", "peak_hbm_bytes")
MESH_ENTRIES = {
    "configs": [{
        "name": "logreg-int8-16m-x4",
        "source": "https://arxiv.org/abs/2206.06022",
        "file": "bench/configs/logreg-int8-16m-x4.json", "reduced": [],
        "why": ("the same logistic regression data-parallel over a (1, 4) "
                "mesh: 67,108,864 x 32 rows over 8,192 vDPUs, one chip's "
                "share on each of four chips")}],
    "workloads": [{
        "name": MESH_CELL, "config": "logreg-int8-16m-x4",
        "traffic": "gd100", "chips": 4,
        "why": ("full-batch GD, 100 steps a fit, on four chips: the only "
                "cell whose merge crosses chips, one ICI all-reduce a step; "
                "cell logreg-int8.gd's share a chip")}],
    "per_layer": [{
        "name": "collective_ms_per_step", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "merge", "moves": "fit_s",
        "workloads": [MESH_CELL]}],
}


def make_small_checkout(dest: str) -> str:
    """A copy of ``BENCHMARK.json`` and ``bench/`` with every
    configuration and traffic mix cut to :data:`SMALL` sizes, and the
    four-chip cell's entries added."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for key, entries in MESH_ENTRIES.items():
        bench[key] += entries
    for m in bench["per_layer"]:
        if m["name"] in MESH_METRICS:
            m["workloads"].append(MESH_CELL)
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    for name, over in SMALL.items():
        path = os.path.join(dest, "bench", "configs", f"{name}.json")
        with open(path) as f:
            cfg = json.load(f)
        with open(path, "w") as f:
            json.dump(_merge(cfg, over), f)
    for name, over in SMALL_TRAFFIC.items():
        path = os.path.join(dest, "bench", "traffic", f"{name}.json")
        with open(path) as f:
            t = json.load(f)
        with open(path, "w") as f:
            json.dump(_merge(t, over), f)
    return dest


@pytest.fixture(scope="session")
def small_root(tmp_path_factory):
    return make_small_checkout(str(tmp_path_factory.mktemp("checkout")))
