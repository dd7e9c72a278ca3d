"""The operations and bytes of one local step on one chip, per cell,
against numbers worked by hand from the cells' shapes."""

import os

import pytest

from bench import harness, peaks
from conftest import MESH_CELL

BENCH = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))


def work(cell):
    if cell == MESH_CELL:               # its entries wait (conftest)
        cfg = harness.load_json(os.path.join(
            harness.BENCH_DIR, "configs", "logreg-int8-16m-x4.json"))
        traffic = harness.load_json(os.path.join(
            harness.BENCH_DIR, "traffic", "gd100.json"))
    else:
        files = harness.cell_files(BENCH, cell)
        cfg, traffic = files["cfg"], files["traffic"]
    algo = harness.module(harness.ROOT, "algos", cfg["algo"])
    return algo.work(cfg, traffic)


# 2,048 vDPUs x 8,192 rows = 16,777,216 rows of 32 int8 features a chip:
# two int8 products of 2 x 16,777,216 x 32 ops; the step reads the
# 536,870,912 bytes of rows and 4 + 4 bytes of label and mask a row; the
# kernels read the rows twice, write int32 logits and read int16
# residuals (6 bytes a row)
LOGREG_GD = {
    "step": {"ops": {"int8": 2_147_483_648}, "bytes": 671_088_640},
    "fxp_matmul": {"ops": {"int8": 2_147_483_648},
                   "bytes": 1_174_405_120},
}


@pytest.mark.parametrize("cell, want", [
    ("logreg-int8.gd", LOGREG_GD),
    # four chips of 2,048 vDPUs x 8,192 rows: each chip's step is the
    # one-chip cell's
    ("logreg-int8.gd.x4", LOGREG_GD),
    # 64 rows of each of 2,048 vDPUs: 131,072 rows a step
    ("logreg-int8.sgd64", {
        "step": {"ops": {"int8": 16_777_216}, "bytes": 5_242_880},
        "fxp_matmul": {"ops": {"int8": 16_777_216}, "bytes": 9_175_040}}),
    # 8,388,608 rows of 16 float32 features, k = 8: 4 x 8,388,608 x 8 x 16
    # flops; 64 bytes of features and 4 of mask a row
    ("kmeans.lloyd", {
        "step": {"ops": {"f32": 4_294_967_296}, "bytes": 570_425_344},
        "kmeans_assign": {"ops": {"f32": 4_294_967_296},
                          "bytes": 570_425_344}}),
])
def test_work_per_step(cell, want):
    assert work(cell) == want


def test_least_time_takes_the_larger_bound():
    v5e = peaks.PEAKS["TPU v5 lite"]
    # logreg gd: 671,088,640 B / 819e9 B/s = 819.4 us, against
    # 2,147,483,648 / 393e12 = 5.5 us of int8 products
    assert peaks.least_time_s(LOGREG_GD["step"], v5e) == pytest.approx(
        671_088_640 / 819e9)
    # a compute-bound step: 1e12 bf16 flops, 1 MB
    assert peaks.least_time_s({"ops": {"bf16": 1e12}, "bytes": 1e6},
                              v5e) == pytest.approx(1e12 / 197e12)


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks_for("TPU v9 imaginary")
