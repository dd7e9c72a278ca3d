"""A configuration, a traffic mix, a limits file and a per-layer metric
reader added as new files to a copy of the benchmark are found by the
names ``BENCHMARK.json`` gives them; no file that was there changes
except ``BENCHMARK.json``, which gains entries."""

import hashlib
import json
import os

from bench import harness

CELL = "logreg-int8.tiny"


def digests(root):
    out = {}
    for d, _, files in os.walk(os.path.join(root, "bench")):
        if "__pycache__" in d or ".cache" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def write(path, obj):
    with open(path, "w") as f:
        if isinstance(obj, str):
            f.write(obj)
        else:
            json.dump(obj, f)


def test_new_cell_config_traffic_and_metric(small_root, tmp_path):
    import shutil

    root = str(tmp_path / "copy")
    shutil.copytree(small_root, root,
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    before = digests(root)
    bench_dir = os.path.join(root, "bench")

    cfg = harness.load_json(
        os.path.join(bench_dir, "configs", "logreg-int8-16m.json"))
    cfg.update(name="logreg-int8-tiny", n_vdpus=4)
    cfg["data"] = dict(cfg["data"], rows=1000)
    write(os.path.join(bench_dir, "configs", "logreg-int8-tiny.json"), cfg)
    write(os.path.join(bench_dir, "traffic", "gd3.json"),
          {"driver": "fit_loop", "steps": 3})
    write(os.path.join(bench_dir, "limits", f"{CELL}.json"),
          {"limits": {"w_gap": 1e-3, "loss_gap": 1e-3}})
    write(os.path.join(bench_dir, "metrics", "fits_done.py"),
          "def read(ctx):\n    return ctx.out['completed']\n")

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "logreg-int8-tiny", "source": "https://arxiv.org/abs/2206.06022",
        "file": "bench/configs/logreg-int8-tiny.json", "reduced": [],
        "why": "a test"})
    bench["workloads"].append({"name": CELL, "config": "logreg-int8-tiny",
                               "traffic": "gd3", "chips": 1, "why": "a test"})
    bench["per_layer"].append({
        "name": "fits_done", "unit": "fits", "better": "higher",
        "source": "host_clock", "layer": "entry", "moves": "fit_s",
        "workloads": [CELL]})
    write(os.path.join(root, "BENCHMARK.json"), bench)

    plain, _ = harness.run_cell(CELL, 5, 0.2, False, root=root,
                                require_accelerator=False)
    traced, _ = harness.run_cell(CELL, 5, 0.2, True, root=root,
                                 require_accelerator=False)
    assert plain["correct"] and traced["correct"]
    assert set(plain["metrics"]) == {"fit_s", "setup_s"}
    assert set(traced["metrics"]) == {"fits_done"}
    assert traced["metrics"]["fits_done"]["value"] == traced["attempted"]
    after = digests(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {
        "bench/configs/logreg-int8-tiny.json", "bench/traffic/gd3.json",
        f"bench/limits/{CELL}.json", "bench/metrics/fits_done.py"}
