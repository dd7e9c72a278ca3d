"""The host spans and the runner-build counter a fit leaves in the
profiler's trace.

Each test profiles fits through ``api.fit`` with ``jax.profiler`` into a
temporary directory and reads the ``.xplane.pb`` back with
``ProfileData``: ``pim.fit`` around the call, ``pim.prepare`` around the
workload's ``prepare``, one ``pim.dispatch`` and one ``pim.history`` a
scan chunk (the remainder round included), and one
``pim.runner_build`` event a runner-cache miss.
"""

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import make_cpu_grid
from repro.core.mlalgos import KMeans, LogReg, api


def profiled_spans(log_dir, fn) -> list:
    """``(start_ns, end_ns, name)`` of every ``pim.*`` host event that
    ``fn()`` leaves in a trace, sorted by start."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(log_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            out += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events if e.name.startswith("pim.")]
    return sorted(out)


def inside(span, outer) -> bool:
    return outer[0] <= span[0] and span[1] <= outer[1]


def named(spans, name) -> list:
    return [s for s in spans if s[2] == name]


def chunks(steps: int, merge_every: int, scan_chunk: int) -> int:
    """Runner calls of one fit: the scan chunks of whole merge rounds,
    and one more for a remainder of fewer than ``merge_every`` steps."""
    rounds, rem = divmod(steps, merge_every)
    return -(-rounds // scan_chunk) + (1 if rem else 0)


STEPS = 10


@pytest.fixture(scope="module")
def logreg_data():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(300, 8)).astype(np.float32)
    return X, (X[:, 0] > 0).astype(np.float32)


@pytest.mark.parametrize("merge_every,scan_chunk", [(1, 4), (4, 1)],
                         ids=["cadence1", "cadence4"])
def test_fit_encloses_prepare_dispatch_and_history(tmp_path, logreg_data,
                                                   merge_every, scan_chunk):
    X, y = logreg_data
    grid = make_cpu_grid(8)
    est = LogReg(precision="int8", sigmoid="lut")

    def fit():
        res = api.fit(est, grid, X, y, steps=STEPS, scan_chunk=scan_chunk,
                      merge_every=merge_every)
        jax.block_until_ready(res.state)
        assert len(res.history) == STEPS

    spans = profiled_spans(tmp_path, fit)
    (root,) = named(spans, "pim.fit")
    assert all(inside(s, root) for s in spans)
    assert len(named(spans, "pim.prepare")) == 1
    n = chunks(STEPS, merge_every, scan_chunk)
    assert n == 3           # both cases run more than one runner call
    dispatch = named(spans, "pim.dispatch")
    history = named(spans, "pim.history")
    assert len(dispatch) == len(history) == n
    # each chunk's unpacking follows its runner call, and the binding
    # comes before both
    body = [s[2] for s in spans if s[2] in ("pim.dispatch", "pim.history")]
    assert body == ["pim.dispatch", "pim.history"] * n
    assert named(spans, "pim.prepare")[0][1] <= dispatch[0][0]


def test_runner_build_counts_cache_misses(tmp_path):
    X = np.random.default_rng(1).normal(size=(256, 4)).astype(np.float32)
    grid = make_cpu_grid(8)

    def fit(k):
        jax.block_until_ready(api.fit(KMeans(k=k), grid, X, steps=3).state)

    fit(4)                  # builds and caches the k = 4 runner

    def fits():
        fit(4)              # equal hyperparameters: the cached runner
        fit(3)              # another k: a new runner

    spans = profiled_spans(tmp_path, fits)
    same, changed = named(spans, "pim.fit")
    builds = named(spans, "pim.runner_build")
    assert [b for b in builds if inside(b, same)] == []
    assert len([b for b in builds if inside(b, changed)]) == 1
    assert len(builds) == 1


def test_second_int8_minibatch_fit_builds_no_runner(tmp_path, logreg_data):
    """A second int8 LUT logreg fit with ``batch_size`` binds anew
    (``pim.prepare`` runs inside it, with new scale arrays) and finds
    its runner in the cache: no ``pim.runner_build`` inside its
    ``pim.fit``."""
    X, y = logreg_data
    grid = make_cpu_grid(8)
    est = LogReg(precision="int8", sigmoid="lut")

    def fit():
        res = api.fit(est, grid, X, y, steps=STEPS, batch_size=16)
        jax.block_until_ready(res.state)

    fit()                   # builds and caches the runner

    spans = profiled_spans(tmp_path, fit)
    (root,) = named(spans, "pim.fit")
    assert [s for s in named(spans, "pim.prepare") if inside(s, root)]
    assert named(spans, "pim.runner_build") == []
