"""The benchmark refuses to run where it cannot give a device result:
no TPU, too few chips, a chip missing from the table of peaks, or a
checkout holding only the benchmark's own files."""

import os
import shutil
import subprocess
import sys
import types

import jax
import pytest

from bench import harness

RUN = os.path.join(harness.ROOT, "bench", "run.py")


def run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"), "--workload",
         "kmeans.lloyd", "--seed", "3", "--seconds", "1", "--trace", "0",
         *extra], capture_output=True, text=True, env=env, cwd=cwd,
        timeout=300)


def test_cpu_backend_is_refused():
    p = run_py(harness.ROOT)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_checkout_of_only_the_benchmark_gives_no_result(tmp_path):
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(harness.ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".cache"))
    p = run_py(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout == ""


def fake_devices(kind, n):
    return [types.SimpleNamespace(platform="tpu", device_kind=kind, id=i)
            for i in range(n)]


def test_unknown_device_kind_is_refused(monkeypatch):
    monkeypatch.setattr(jax, "devices", lambda: fake_devices("TPU v9", 1))
    with pytest.raises(harness.Refused, match="no published peaks"):
        harness.devices_for(1, True)


def test_too_few_chips_are_refused(monkeypatch):
    monkeypatch.setattr(jax, "devices",
                        lambda: fake_devices("TPU v5 lite", 1))
    with pytest.raises(harness.Refused, match="needs 4 chips"):
        harness.devices_for(4, True)


def test_known_chip_gets_its_peaks(monkeypatch):
    monkeypatch.setattr(jax, "devices",
                        lambda: fake_devices("TPU v5 lite", 4))
    devs, peaks = harness.devices_for(4, True)
    assert len(devs) == 4 and peaks["hbm_bytes_per_s"] == 819e9
