"""Linear SVM (hinge loss) by subgradient descent on the PIM grid.

PIM-Opt (arXiv 2404.07164) evaluates exactly two workloads on the real
2,524-DPU system: logistic regression and **linear SVM** — same
DPU-resident data flow, different loss.  This module is that second
workload as a :class:`~repro.core.mlalgos.api.Workload` plugin, and the
existence proof that the protocol makes a new estimator a ~100-line
file: the scan engine, merge cadence/plans, minibatch sampling, the
Trainer, dry-run lowering and the benchmarks all apply with zero
threading.

Per resident row (label mapped to ±1):

    margin m = y·(x·w),  hinge = max(0, 1 − m)
    subgrad g = −y·x  where m < 1, else 0   (+ L2 on the host)

The fixed-point path is the hybrid-precision row format of
:mod:`~repro.core.mlalgos.fixed_point` (insight I1), as in
linreg/logreg.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mlalgos import api
from repro.core.mlalgos.fixed_point import Precision, row_format
from repro.core.pim import PimGrid


@dataclasses.dataclass
class SVMResult:
    w: jax.Array
    history: list             # per-step dicts: loss (mean hinge + L2 term)
    precision: str


@dataclasses.dataclass(frozen=True)
class LinearSVM(api.Workload):
    """Hinge-loss linear SVM; labels may arrive as {0,1} or {−1,+1}
    (``prepare`` maps them to ±1)."""

    lr: float = 0.1
    l2: float = 1e-3          # the SVM regularizer (C = 1/(l2·n))
    precision: Precision = "fp32"

    name = "svm"

    def prepare(self, grid: PimGrid, X, y=None):
        ys = jnp.where(jnp.asarray(y) > 0, 1.0, -1.0).astype(jnp.float32)
        Xv, scales = row_format(self.precision).place(X)
        data, n = grid.shard_rows(Xv, ys)
        return data, n, {"n": n, "d": X.shape[1], **scales}

    def stream_consts(self, stream):
        return {"n": stream.n_rows, "d": stream.n_features,
                **row_format(self.precision).stream_scales(
                    stream.feature_absmax)}

    def stream_transform(self, consts, X_rows, y_rows):
        # same ±1 label map as prepare, applied per window
        ys = np.where(np.asarray(y_rows) > 0, 1.0, -1.0).astype(np.float32)
        return row_format(self.precision).stage(consts, X_rows), ys

    def init_state(self, consts):
        return jnp.zeros((consts["d"],), jnp.float32)

    def local_step(self, consts, w, sl):
        fmt = row_format(self.precision)
        ys = sl["y0"]
        z = fmt.matmul(consts, sl["X"], w)
        active = (ys * z < 1.0).astype(jnp.float32) * sl["w"]
        # hinge subgradient: −Σ_active y·x  (an MXU dot, like the other
        # workloads' gradient contraction)
        g = fmt.rmatmul(consts, sl["X"], -(ys * active))
        hinge = jnp.maximum(0.0, 1.0 - ys * z) * sl["w"]
        return {"g": g, "loss": jnp.sum(hinge)}

    def update(self, consts, w, merged):
        n = consts["n"]
        g = merged["g"] / n + self.l2 * w
        loss = merged["loss"] / n + 0.5 * self.l2 * jnp.sum(w * w)
        return w - self.lr * g, {"loss": loss}

    def eval(self, state, X, y=None) -> dict:
        out = {}
        if y is not None:
            out["accuracy"] = svm_accuracy(state, X, y)
        return out

    def predict(self, state, X):
        """Serving decision values (sign = class): ``local_step``'s
        forward on the request rows in the row format.  fp32 is
        bit-exact with the :func:`svm_predict` ``eval`` uses."""
        fmt = row_format(self.precision)
        Xv, scales = fmt.request(jnp.asarray(X))
        return fmt.matmul(scales, Xv, state)

    def spec_fns(self, *, features: int, rows: int):
        """Spec-level engine fns for ``launch.dryrun_pim`` (the row
        format's unit scales; no resident data materialized):
        ``(local_fn, update_fn, state0, consts)``, as
        ``LogReg.spec_fns``."""
        consts = {"n": rows, "d": features,
                  **row_format(self.precision).unit_scales(features)}
        program = api.Program.assemble(self, None, None, rows, consts)
        return (program.local_fn, program.update_fn, program.state0,
                program.array_consts)


def train_svm(grid: PimGrid, X: jax.Array, y: jax.Array, *,
              lr: float = 0.1, steps: int = 100, l2: float = 1e-3,
              precision: Precision = "fp32", engine: str = "scan",
              merge_every: int = 1, overlap_merge: bool = False,
              merge_compression=None, merge_state: dict | None = None,
              merge_plan=None, batch_size: int | None = None,
              sample_seed: int = 0) -> SVMResult:
    """Full option surface for free via the Workload protocol — cadence,
    merge plans, minibatching (PIM-Opt trains SVM exactly this way:
    minibatch SGD with local update cadence)."""
    res = api.fit(LinearSVM(lr=lr, l2=l2, precision=precision),
                  grid, X, y, steps=steps, engine=engine,
                  merge_every=merge_every, overlap_merge=overlap_merge,
                  merge_compression=merge_compression,
                  merge_state=merge_state, merge_plan=merge_plan,
                  batch_size=batch_size, sample_seed=sample_seed)
    return SVMResult(w=res.state, history=res.history,
                     precision=precision)


def svm_predict(w: jax.Array, X: jax.Array) -> jax.Array:
    """Decision values (sign = class)."""
    return X @ w


def svm_accuracy(w: jax.Array, X: jax.Array, y: jax.Array) -> float:
    """Accuracy against {0,1} or ±1 labels."""
    ys = jnp.where(jnp.asarray(y) > 0, 1.0, -1.0)
    return float(jnp.mean(jnp.sign(svm_predict(w, X)) == ys))
