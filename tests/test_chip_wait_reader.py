"""The benchmark's ``chip_wait_ms_per_step`` reader on hand-made chip
traces: the exposed collective time inside the runner's executions, the
longest chip's less the shortest chip's, per local step."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import trace_reduce as tr  # noqa: E402
from bench.metrics import chip_wait_ms_per_step as reader  # noqa: E402

STEP_AR = "%all-reduce.3 = (f32[32]{0}, f32[]) all-reduce(f32[32]{0} %f)"
SCALE_AR = "%all-reduce = f32[1,32]{1,0} all-reduce(f32[1,32]{1,0} %b)"
FUSION = "%fusion.7 = f32[32]{0} fusion(s8[8,32]{1,0} %x), kind=kLoop"
RUNNER = "jit_runner(1234)"
PREPARE = "jit__reduce_max(5678)"


def chip(index, step_ars, scale_ar):
    """A chip that runs two steps in one runner call over [0, 500), and
    ``prepare``'s scale maximum in its own program over [600, 700)."""
    ops = [(0, 100, FUSION), (200, 300, FUSION)]
    ops += [(s, e, STEP_AR) for s, e in step_ars]
    ops.append((*scale_ar, SCALE_AR))
    return tr.Device(index=index, ops=sorted(ops),
                     modules=[(0, 500, RUNNER), (600, 700, PREPARE)])


def ctx(devices, completed=1, steps=2):
    return types.SimpleNamespace(
        out={"completed": completed, "steps_per_fit": steps},
        trace=types.SimpleNamespace(devices=devices, window=(0, 1000)))


# the early chip reaches the first all-reduce 60 ns before the last one
EARLY = chip(0, [(100, 180), (300, 310)], (620, 690))
LATE = chip(1, [(160, 180), (300, 305)], (620, 630))


def test_runner_collectives_only():
    # prepare's all-reduce (70 and 10 ns) is left out; an all-reduce
    # that overlaps other work counts only where nothing else runs
    assert reader.runner_collective_ns(EARLY, (0, 1000)) == (80 + 10, 2)
    assert reader.runner_collective_ns(LATE, (0, 1000)) == (20 + 5, 2)
    overlapped = chip(2, [(50, 150)], (620, 630))
    assert reader.runner_collective_ns(overlapped, (0, 1000)) == (50, 1)


def test_longest_less_shortest_per_step():
    assert reader.read(ctx([EARLY, LATE])) == pytest.approx(
        ((80 + 10) - (20 + 5)) / 2 / 1e6)
    assert reader.read(ctx([LATE, EARLY, LATE], completed=2)) == (
        pytest.approx(((80 + 10) - (20 + 5)) / 4 / 1e6))


@pytest.mark.parametrize("devices", [[EARLY], [], "no_collective"])
def test_reads_nothing_without_an_exchange(devices):
    if devices == "no_collective":
        bare = tr.Device(index=0, ops=[(0, 100, FUSION)],
                         modules=[(0, 500, RUNNER)])
        devices = [bare, bare]
    assert reader.read(ctx(devices)) is None
