"""Linear regression by batch gradient descent on the PIM grid.

Paper workload #1.  Each DPU computes the partial gradient
``g_p = X_pᵀ(X_p w − y_p)`` over its resident rows; the host merges the
partials and applies the GD step.  Three numeric paths, as in the paper:
``fp32`` (the reference float path) and the hybrid-precision ``int16`` /
``int8`` fixed point, whose rows, dots and rescales are
:mod:`~repro.core.mlalgos.fixed_point`'s.  Beside quantized rows the
labels ride as 16-bit fixed point too, with one scale (``y_scale``).

Implemented as a :class:`~repro.core.mlalgos.api.Workload` plugin —
``train_linreg`` and ``make_linreg_step`` are thin wrappers over the
protocol, so every engine axis (cadence, merge plans, ``batch_size``
minibatching) applies without algorithm-side threading.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core.mlalgos import api, fixed_point as fxp
from repro.core.mlalgos.fixed_point import Precision, row_format
from repro.core.pim import PimGrid

_WIDE_LABELS = fxp.FixedRows(16, key="y_scale", axis=None)


@dataclasses.dataclass
class LinRegResult:
    w: jax.Array
    history: list          # per-step dicts: loss
    precision: str


@dataclasses.dataclass(frozen=True)
class LinReg(api.Workload):
    """GD linear regression (optionally hybrid fixed point)."""

    lr: float = 0.1
    precision: Precision = "fp32"
    l2: float = 0.0

    name = "linreg"

    def _formats(self):
        """The row format and the label format: labels stay float on
        fp32 and are 16-bit fixed point beside quantized rows."""
        fmt = row_format(self.precision)
        return fmt, fxp.FLOAT if fmt is fxp.FLOAT else _WIDE_LABELS

    def prepare(self, grid: PimGrid, X, y=None):
        fmt, labels = self._formats()
        Xv, x_scales = fmt.place(X)
        yv, y_scales = labels.place(y)
        data, n = grid.shard_rows(Xv, yv)
        return data, n, {"n": n, "d": X.shape[1], **x_scales, **y_scales}

    def stream_consts(self, stream):
        """Out-of-core constants: the quantized paths derive their
        per-feature / label scales from one-pass host statistics over
        the *whole* stream, so every rotation window quantizes on the
        same grid the resident path would."""
        fmt, labels = self._formats()
        return {"n": stream.n_rows, "d": stream.n_features,
                **fmt.stream_scales(stream.feature_absmax),
                **labels.stream_scales(stream.label_absmax)}

    def stream_transform(self, consts, X_rows, y_rows):
        fmt, labels = self._formats()
        return fmt.stage(consts, X_rows), labels.stage(consts, y_rows)

    def init_state(self, consts):
        return jnp.zeros((consts["d"],), jnp.float32)

    def local_step(self, consts, w, sl):
        fmt, labels = self._formats()
        r = (fmt.matmul(consts, sl["X"], w)
             - labels.dequantize(consts, sl["y0"])) * sl["w"]  # mask padding
        g = fmt.rmatmul(consts, sl["X"], r)
        return {"g": g, "loss": jnp.sum(r * r)}

    def update(self, consts, w, merged):
        n = consts["n"]
        g = merged["g"] / n + self.l2 * w
        loss = merged["loss"] / n
        return w - self.lr * g, {"loss": loss}

    def eval(self, state, X, y=None) -> dict:
        pred = linreg_predict(state, X)
        out = {}
        if y is not None:
            out["mse"] = float(jnp.mean((pred - y) ** 2))
        return out

    def predict(self, state, X):
        """Serving forward pass: ``local_step``'s forward on the
        request rows in the row format.  fp32 is bit-exact with the
        :func:`linreg_predict` ``eval`` uses.  Pad-invariant: zero rows
        never move a per-feature absmax."""
        fmt = row_format(self.precision)
        Xv, scales = fmt.request(jnp.asarray(X))
        return fmt.matmul(scales, Xv, state)


def make_linreg_step(grid: PimGrid, X: jax.Array, y: jax.Array, *,
                     lr: float = 0.1, precision: Precision = "fp32",
                     l2: float = 0.0):
    """Build the grid-engine pieces for one linreg problem.

    Returns ``(data, n, local_fn, update_fn, w0)`` ready for
    ``grid.fit`` — the bound :class:`LinReg` program's triple, with its
    array constants bound into two-argument functions.  Exposed
    separately from :func:`train_linreg` so benchmarks can build the
    closures *once* and sweep ``fit`` options (engine, cadence) against
    stable compile-cache keys — re-building per timed call would
    measure retracing, not step rate (the quantized paths' functions
    capture their scale arrays here, so their keys never repeat across
    builds; ``api.fit`` passes the scales as an operand instead).
    """
    from repro.distributed.merge_plan import bind_consts

    program = LinReg(lr=lr, precision=precision, l2=l2).bind(grid, X, y)
    consts = program.array_consts
    return (program.data, program.n,
            bind_consts(program.local_fn, consts),
            bind_consts(program.update_fn, consts), program.state0)


def train_linreg(grid: PimGrid, X: jax.Array, y: jax.Array, *,
                 lr: float = 0.1, steps: int = 100,
                 precision: Precision = "fp32",
                 l2: float = 0.0, engine: str = "scan",
                 merge_every: int = 1, overlap_merge: bool = False,
                 merge_compression=None,
                 merge_state: dict | None = None,
                 merge_plan=None, batch_size: int | None = None,
                 sample_seed: int = 0) -> LinRegResult:
    """``merge_every=k`` runs k vDPU-local GD steps between host merges
    (PIM-Opt's local-update axis); ``k=1`` is the paper's
    merge-per-step loop, bit-exact with the PR 1 engine.
    ``merge_plan`` is the canonical composed spelling (cadence ×
    overlap × compression × outer optimizer — see
    ``distributed.merge_plan``); ``overlap_merge``/``merge_compression``
    remain as thin constructors for it.  ``batch_size=b`` samples b of
    the resident per-vDPU rows each local step (``core.minibatch``;
    ``None`` = the untouched full-batch path).  All knobs off
    reproduces the exact engine bit-for-bit."""
    res = api.fit(LinReg(lr=lr, precision=precision, l2=l2), grid, X, y,
                  steps=steps, engine=engine, merge_every=merge_every,
                  overlap_merge=overlap_merge,
                  merge_compression=merge_compression,
                  merge_state=merge_state, merge_plan=merge_plan,
                  batch_size=batch_size, sample_seed=sample_seed)
    return LinRegResult(w=res.state, history=res.history,
                        precision=precision)


def linreg_predict(w: jax.Array, X: jax.Array) -> jax.Array:
    return X @ w


def closed_form(X: jax.Array, y: jax.Array, l2: float = 0.0) -> jax.Array:
    """Normal-equation oracle used by tests."""
    d = X.shape[1]
    A = X.T @ X + l2 * X.shape[0] * jnp.eye(d)
    return jnp.linalg.solve(A, X.T @ y)
