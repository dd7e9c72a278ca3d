"""Multinomial (softmax) logistic regression on the PIM grid.

The paper's logistic regression is binary; real PIM deployments
(multi-class Criteo-style tabular data, the decision tree's own label
space) want the C-class generalisation.  Same DPU data flow: each vDPU
computes a partial gradient ``G_p = X_pᵀ(softmax(X_p W) − onehot(y_p))``
over its resident rows, the host merges and steps.  A second
:class:`~repro.core.mlalgos.api.Workload` plugin proof-point: state is
a *matrix*, labels are integers, and nothing outside this file changes.

The softmax reuses the paper's insight I2 machinery: ``softmax="lut"``
evaluates exp through a lookup table (``core.lut.exp_lut``) on the
``lut_activation`` Pallas kernel — shifted logits ``z − max(z)`` are
≤ 0, so the table is one-sided and endpoint clamping is exact enough
for training (the sigmoid saturation argument).  The fixed-point path
is the binary workloads' row format
(:mod:`~repro.core.mlalgos.fixed_point`), its dots taking the
``(d, C)`` weight matrix.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Literal

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.mlalgos import api
from repro.core.mlalgos.fixed_point import Precision, row_format
from repro.core.pim import PimGrid
from repro.core import lut as lut_mod
from repro.kernels import dispatch

Softmax = Literal["exact", "lut"]


@dataclasses.dataclass
class MultinomialResult:
    W: jax.Array              # (d, n_classes)
    history: list             # per-step dicts: loss (mean cross-entropy)
    precision: str
    softmax: str


def make_softmax(kind: Softmax, n_entries: int = 1024):
    """Row-wise softmax over shifted logits; the ``lut`` variant
    evaluates exp via the one-sided table on the Pallas LUT kernel.
    The same function object for the same arguments, as
    ``logreg.make_sigmoid``."""
    return _softmax(kind, int(n_entries))


@functools.cache
def _softmax(kind: Softmax, n_entries: int):
    if kind == "exact":
        return lambda z: jax.nn.softmax(z, axis=-1)
    if kind == "lut":
        with jax.ensure_compile_time_eval():
            table = lut_mod.exp_lut(n_entries=n_entries)

        def lut_softmax(z):
            shifted = z - jax.lax.stop_gradient(
                jnp.max(z, axis=-1, keepdims=True))
            e = dispatch.lut_apply(table, shifted)
            return e / jnp.sum(e, axis=-1, keepdims=True)

        return lut_softmax
    raise ValueError(kind)


@dataclasses.dataclass(frozen=True)
class MultinomialLogReg(api.Workload):
    """C-class softmax regression; state = the (d, C) weight matrix."""

    n_classes: int = 4
    lr: float = 0.5
    precision: Precision = "fp32"
    softmax: Softmax = "exact"
    lut_entries: int = 1024
    l2: float = 0.0

    name = "multinomial"

    def prepare(self, grid: PimGrid, X, y=None):
        yi = jnp.asarray(y, jnp.int32)
        sm = make_softmax(self.softmax, self.lut_entries)
        Xv, scales = row_format(self.precision).place(X)
        data, n = grid.shard_rows(Xv, yi)
        return data, n, {"n": n, "d": X.shape[1], "sm": sm, **scales}

    def stream_consts(self, stream):
        return {"n": stream.n_rows, "d": stream.n_features,
                "sm": make_softmax(self.softmax, self.lut_entries),
                **row_format(self.precision).stream_scales(
                    stream.feature_absmax)}

    def stream_transform(self, consts, X_rows, y_rows):
        yi = np.asarray(y_rows, np.int32)     # same cast as prepare
        return row_format(self.precision).stage(consts, X_rows), yi

    def init_state(self, consts):
        return jnp.zeros((consts["d"], self.n_classes), jnp.float32)

    def local_step(self, consts, W, sl):
        fmt = row_format(self.precision)
        onehot = jax.nn.one_hot(sl["y0"], self.n_classes,
                                dtype=jnp.float32)
        Z = fmt.matmul(consts, sl["X"], W)                    # (R, C)
        P = consts["sm"](Z)
        R = (P - onehot) * sl["w"][:, None]
        G = fmt.rmatmul(consts, sl["X"], R)                   # (d, C)
        # cross-entropy with the exact log-softmax for metric reporting
        # (same convention as binary logreg's exact-log BCE)
        logp = jax.nn.log_softmax(Z, axis=-1)
        loss = -jnp.sum(sl["w"] * jnp.sum(onehot * logp, axis=-1))
        return {"g": G, "loss": loss}

    def update(self, consts, W, merged):
        n = consts["n"]
        G = merged["g"] / n + self.l2 * W
        return W - self.lr * G, {"loss": merged["loss"] / n}

    def eval(self, state, X, y=None) -> dict:
        out = {}
        if y is not None:
            out["accuracy"] = multinomial_accuracy(state, X, y)
        return out

    def predict(self, state, X):
        """Serving class probabilities ``(n, C)`` through the configured
        softmax (the ``lut`` variant evaluates exp on the Pallas LUT
        kernel, as in training).  ``exact``+fp32 is bit-exact with
        :func:`multinomial_predict`; the logits are ``local_step``'s
        forward on the request rows in the row format."""
        sm = make_softmax(self.softmax, self.lut_entries)
        fmt = row_format(self.precision)
        Xv, scales = fmt.request(jnp.asarray(X))
        return sm(fmt.matmul(scales, Xv, state))

    def spec_fns(self, *, features: int, rows: int):
        """Spec-level engine fns for ``launch.dryrun_pim`` (the row
        format's unit scales; no resident data materialized):
        ``(local_fn, update_fn, state0, consts)``, as
        ``LogReg.spec_fns``."""
        consts = {"n": rows, "d": features,
                  "sm": make_softmax(self.softmax, self.lut_entries),
                  **row_format(self.precision).unit_scales(features)}
        program = api.Program.assemble(self, None, None, rows, consts)
        return (program.local_fn, program.update_fn, program.state0,
                program.array_consts)


def train_multinomial(grid: PimGrid, X: jax.Array, y: jax.Array, *,
                      n_classes: int, lr: float = 0.5, steps: int = 100,
                      precision: Precision = "fp32",
                      softmax: Softmax = "exact",
                      lut_entries: int = 1024, l2: float = 0.0,
                      engine: str = "scan", merge_every: int = 1,
                      overlap_merge: bool = False,
                      merge_compression=None,
                      merge_state: dict | None = None,
                      merge_plan=None, batch_size: int | None = None,
                      sample_seed: int = 0) -> MultinomialResult:
    """Full option surface for free via the Workload protocol."""
    res = api.fit(
        MultinomialLogReg(n_classes=n_classes, lr=lr,
                          precision=precision, softmax=softmax,
                          lut_entries=lut_entries, l2=l2),
        grid, X, y, steps=steps, engine=engine, merge_every=merge_every,
        overlap_merge=overlap_merge, merge_compression=merge_compression,
        merge_state=merge_state, merge_plan=merge_plan,
        batch_size=batch_size, sample_seed=sample_seed)
    return MultinomialResult(W=res.state, history=res.history,
                             precision=precision, softmax=softmax)


def multinomial_predict(W: jax.Array, X: jax.Array) -> jax.Array:
    """Class probabilities (n, C)."""
    return jax.nn.softmax(X @ W, axis=-1)


def multinomial_accuracy(W: jax.Array, X: jax.Array,
                         y: jax.Array) -> float:
    pred = jnp.argmax(X @ W, axis=-1)
    return float(jnp.mean(pred == jnp.asarray(y)))
