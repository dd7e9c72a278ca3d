"""The controls, at a size a test run holds: the plain reference put in
the program's place in the next precision below the configuration's
(int4 rows for int8 logreg, bfloat16 for float32 k-means) fails at
least one of its cell's limits, and so does each planted fault; one fit
of the program itself passes every limit.

On the chip the same readings come from ``bench/readings.py`` at the
cells' own sizes (PERF.md gives them)."""

import pytest

from bench import harness, readings

CELLS = ["logreg-int8.gd", "kmeans.lloyd", "logreg-int8.sgd64",
         "logreg-int8.gd.x4"]
SEEDS = [2 ** 31 + 7, 13, 2 ** 31 + 1_000_003]


def passes(numbers: dict, limits: dict) -> bool:
    return all(harness.within(v, limits.get(k)) for k, v in numbers.items())


@pytest.mark.parametrize("cell", CELLS)
def test_control_and_faults_fail_program_passes(small_root, cell):
    limits = harness.cell_files(
        harness.load_json(f"{small_root}/BENCHMARK.json"), cell,
        small_root)["limits"]
    assert limits, f"{cell} has no limits"
    seen = set()
    for line in readings.readings(cell, SEEDS, True, root=small_root,
                                  require_accelerator=False):
        seen.add(line["variant"])
        ok = passes(line["numbers"], limits)
        assert ok == (line["variant"] == "program"), line
    assert {"program", "control", "unchanged", "half_batch"} <= seen
    cfg = harness.cell_files(harness.load_json(f"{small_root}/BENCHMARK.json"),
                             cell, small_root)["cfg"]
    assert ("no_exchange" in seen) == (cfg["chips"] > 1)
