"""A run with the timed path broken underneath it comes out not correct.

Each test drives a whole run of a cell (set-up, window, comparison with
the plain reference) at the sizes of ``conftest.SMALL`` on the CPU,
past the harness's look for a chip, with one fault planted in the
program: a step that returns its state unchanged, half of the rows left
out with the mean taken over the rest, the answer altered where the fit
produces it, and, in the four-chip cell, the exchange between chips
left out.
"""

import jax
import pytest

from bench import harness
from repro.core.mlalgos import KMeans, LogReg, api

CELLS = ["logreg-int8.gd", "kmeans.lloyd", "logreg-int8.sgd64",
         "logreg-int8.gd.x4"]
MESH_CELLS = ["logreg-int8.gd.x4"]
SEED = 2 ** 31 + 101


def run(root, cell):
    result, _ = harness.run_cell(cell, SEED, 0.2, False, root=root,
                                 require_accelerator=False)
    return result


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(small_root, cell):
    result = run(small_root, cell)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1


def _unchanged(monkeypatch):
    def logreg_update(self, consts, w, merged):
        return w, {"loss": merged["loss"] / consts["n"]}

    def kmeans_update(self, consts, c, merged):
        return c, {"sse": merged["sse"], "moved": 0.0 * merged["sse"]}

    monkeypatch.setattr(LogReg, "update", logreg_update)
    monkeypatch.setattr(KMeans, "update", kmeans_update)


def _half_batch(monkeypatch):
    for cls in (LogReg, KMeans):
        orig = cls.prepare

        def prepare(self, grid, X, y=None, _orig=orig):
            half = X.shape[0] // 2
            return _orig(self, grid, X[:half],
                         None if y is None else y[:half])

        monkeypatch.setattr(cls, "prepare", prepare)


def _altered(monkeypatch):
    orig = api.Program._run

    def _run(self, **kw):
        res = orig(self, **kw)
        res.state = res.state * 1.001
        return res

    monkeypatch.setattr(api.Program, "_run", _run)


def _no_exchange(monkeypatch):
    # every psum between chips returns the chip's own partial
    monkeypatch.setattr(jax.lax, "psum", lambda x, axis_name, **kw: x)


FAULTS = {"unchanged": _unchanged, "half_batch": _half_batch,
          "altered": _altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(small_root, monkeypatch, cell, fault):
    FAULTS[fault](monkeypatch)
    result = run(small_root, cell)
    assert not result["correct"], result["checks"]


@pytest.mark.parametrize("cell", MESH_CELLS)
def test_exchange_left_out_is_not_correct(small_root, monkeypatch, cell):
    _no_exchange(monkeypatch)
    result = run(small_root, cell)
    assert not result["correct"], result["checks"]
