"""Logistic regression by gradient descent on the PIM grid.

Paper workload #2.  Identical data flow to linear regression plus the
sigmoid — which is the paper's headline LUT result (insight I2): DPUs have
no transcendental unit, so the paper evaluates sigmoid three ways and finds
the lookup table wins:

  * ``exact``  — jnp sigmoid (reference; what CPU/GPU run),
  * ``lut``    — nearest/interp LUT (the paper's winning variant),
  * ``taylor`` — truncated series (the paper's losing baseline).

Combined with the fixed-point rows of
:mod:`~repro.core.mlalgos.fixed_point` this reproduces the paper's
accuracy parity table for logistic regression.  Implemented as a
:class:`~repro.core.mlalgos.api.Workload` plugin; ``train_logreg`` is a
thin wrapper over the protocol.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.mlalgos import api
from repro.core.mlalgos.fixed_point import Precision, row_format
from repro.core.pim import PimGrid
from repro.core import lut as lut_mod
from repro.kernels import dispatch

Sigmoid = Literal["exact", "lut", "lut_interp", "taylor"]


@dataclasses.dataclass
class LogRegResult:
    w: jax.Array
    history: list
    precision: str
    sigmoid: str


def make_sigmoid(kind: Sigmoid, n_entries: int = 1024):
    """The sigmoid variant ``kind``: the same function object for the
    same arguments, so a step function that captures it keeps its
    compile-cache key from one bind to the next."""
    return _sigmoid(kind, int(n_entries))


@functools.cache
def _sigmoid(kind: Sigmoid, n_entries: int):
    if kind == "exact":
        return jax.nn.sigmoid
    if kind == "taylor":
        return lut_mod.taylor_sigmoid
    # a concrete table even when first asked for inside a trace (the
    # serving runner jits predict): the memo outlives every trace
    with jax.ensure_compile_time_eval():
        table = lut_mod.sigmoid_lut(n_entries=n_entries)
    if kind == "lut":
        # nearest-entry LUT routes through the lut_activation Pallas
        # kernel (one-hot @ table on the MXU)
        return lambda x: dispatch.lut_apply(table, x)
    if kind == "lut_interp":
        return lambda x: lut_mod.lut_lookup_interp(table, x)
    raise ValueError(kind)


@dataclasses.dataclass(frozen=True)
class LogReg(api.Workload):
    """GD binary logistic regression (LUT sigmoid variants, hybrid
    fixed point)."""

    lr: float = 0.5
    precision: Precision = "fp32"
    sigmoid: Sigmoid = "exact"
    lut_entries: int = 1024
    l2: float = 0.0

    name = "logreg"

    def prepare(self, grid: PimGrid, X, y=None):
        sig = make_sigmoid(self.sigmoid, self.lut_entries)
        Xv, scales = row_format(self.precision).place(X)
        data, n = grid.shard_rows(Xv, y)
        return data, n, {"n": n, "d": X.shape[1], "sig": sig, **scales}

    def stream_consts(self, stream):
        return {"n": stream.n_rows, "d": stream.n_features,
                "sig": make_sigmoid(self.sigmoid, self.lut_entries),
                **row_format(self.precision).stream_scales(
                    stream.feature_absmax)}

    def stream_transform(self, consts, X_rows, y_rows):
        return row_format(self.precision).stage(consts, X_rows), y_rows

    def init_state(self, consts):
        return jnp.zeros((consts["d"],), jnp.float32)

    def local_step(self, consts, w, sl):
        fmt = row_format(self.precision)
        z = fmt.matmul(consts, sl["X"], w)
        p = consts["sig"](z)
        r = (p - sl["y0"]) * sl["w"]
        g = fmt.rmatmul(consts, sl["X"], r)
        # BCE loss with the *exact* log for metric reporting (the paper
        # also reports accuracy computed on the host in float).
        eps = 1e-7
        pe = jnp.clip(jax.nn.sigmoid(z), eps, 1 - eps)
        loss = -jnp.sum(sl["w"] * (sl["y0"] * jnp.log(pe)
                                   + (1 - sl["y0"]) * jnp.log(1 - pe)))
        return {"g": g, "loss": loss}

    def update(self, consts, w, merged):
        n = consts["n"]
        g = merged["g"] / n + self.l2 * w
        return w - self.lr * g, {"loss": merged["loss"] / n}

    def eval(self, state, X, y=None) -> dict:
        out = {}
        if y is not None:
            out["accuracy"] = accuracy(state, X, y)
        return out

    def predict(self, state, X):
        """Serving probabilities through the configured sigmoid (exact /
        LUT / taylor — the LUT variant routes through the
        ``lut_activation`` Pallas kernel exactly as in training).  The
        ``exact``+fp32 configuration is bit-exact with
        :func:`logreg_predict`; the logits are ``local_step``'s forward
        on the request rows in the row format."""
        sig = make_sigmoid(self.sigmoid, self.lut_entries)
        fmt = row_format(self.precision)
        Xv, scales = fmt.request(jnp.asarray(X))
        return sig(fmt.matmul(scales, Xv, state))

    def spec_fns(self, *, features: int, rows: int):
        """Spec-level engine fns for lowering without resident data
        (``launch.dryrun_pim``): the row format's unit scales, the
        configured sigmoid.  Returns ``(local_fn, update_fn, state0,
        consts)``: the functions take ``consts``, the array constants,
        as their third argument, as in ``api.fit``."""
        consts = {"n": rows, "d": features,
                  "sig": make_sigmoid(self.sigmoid, self.lut_entries),
                  **row_format(self.precision).unit_scales(features)}
        program = api.Program.assemble(self, None, None, rows, consts)
        return (program.local_fn, program.update_fn, program.state0,
                program.array_consts)


def train_logreg(grid: PimGrid, X: jax.Array, y: jax.Array, *,
                 lr: float = 0.5, steps: int = 100,
                 precision: Precision = "fp32",
                 sigmoid: Sigmoid = "exact",
                 lut_entries: int = 1024,
                 l2: float = 0.0, engine: str = "scan",
                 merge_every: int = 1, overlap_merge: bool = False,
                 merge_compression=None,
                 merge_state: dict | None = None,
                 merge_plan=None, batch_size: int | None = None,
                 sample_seed: int = 0) -> LogRegResult:
    """``merge_every=k`` runs k vDPU-local GD steps between host merges;
    ``k=1`` is bit-exact with the PR 1 merge-per-step engine.
    ``merge_plan`` composes the full merge configuration
    (``distributed.merge_plan``); ``overlap_merge``/
    ``merge_compression`` are its legacy constructors.  ``batch_size=b``
    samples b resident rows per vDPU per local step (``None`` = the
    untouched full-batch path).  All off is exact."""
    res = api.fit(
        LogReg(lr=lr, precision=precision, sigmoid=sigmoid,
               lut_entries=lut_entries, l2=l2),
        grid, X, y, steps=steps, engine=engine, merge_every=merge_every,
        overlap_merge=overlap_merge, merge_compression=merge_compression,
        merge_state=merge_state, merge_plan=merge_plan,
        batch_size=batch_size, sample_seed=sample_seed)
    return LogRegResult(w=res.state, history=res.history,
                        precision=precision, sigmoid=sigmoid)


def logreg_predict(w: jax.Array, X: jax.Array) -> jax.Array:
    """Probabilities."""
    return jax.nn.sigmoid(X @ w)


def accuracy(w: jax.Array, X: jax.Array, y: jax.Array) -> float:
    pred = (logreg_predict(w, X) > 0.5).astype(y.dtype)
    return float(jnp.mean(pred == y))
