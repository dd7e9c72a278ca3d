"""Linear regression by batch gradient descent on the PIM grid.

Paper workload #1.  Each DPU computes the partial gradient
``g_p = X_pᵀ(X_p w − y_p)`` over its resident rows; the host merges the
partials and applies the GD step.  Three numeric paths, as in the paper:

  * ``fp32``   — reference float path (what a CPU/GPU would run),
  * ``int16`` / ``int8`` — hybrid-precision fixed point: the *dataset copy*
    is quantized once (per-feature scales), the dot products run in
    integers with int32 accumulation, and only the merged gradient is
    rescaled to float for the update (paper's "hybrid precision").

Implemented as a :class:`~repro.core.mlalgos.api.Workload` plugin —
``train_linreg`` and ``make_linreg_step`` are thin wrappers over the
protocol, so every engine axis (cadence, merge plans, ``batch_size``
minibatching) applies without algorithm-side threading.
"""

from __future__ import annotations

import dataclasses
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.mlalgos import api
from repro.core.pim import PimGrid
from repro.core import quantize as qz
from repro.kernels import dispatch

Precision = Literal["fp32", "int16", "int8"]


@dataclasses.dataclass
class LinRegResult:
    w: jax.Array
    history: list          # per-step dicts: loss
    precision: str


def _quantize_dataset(X, y, bits):
    Xq = qz.quantize_symmetric(X, bits=bits, axis=0)      # per-feature scale
    yq = qz.quantize_symmetric(y, bits=16)                 # labels wide
    return Xq, yq


@dataclasses.dataclass(frozen=True)
class LinReg(api.Workload):
    """GD linear regression (optionally hybrid fixed point)."""

    lr: float = 0.1
    precision: Precision = "fp32"
    l2: float = 0.0

    name = "linreg"

    def prepare(self, grid: PimGrid, X, y=None):
        d = X.shape[1]
        if self.precision == "fp32":
            data, n = grid.shard_rows(X, y)
            consts = {"n": n, "d": d}
        else:
            bits = {"int16": 16, "int8": 8}[self.precision]
            Xq, yq = _quantize_dataset(X, y, bits)
            # Resident copy is the quantized one (paper: banks hold
            # fixed point).  The scales are trace-time constants.
            data, n = grid.shard_rows(Xq.values, yq.values)
            consts = {"n": n, "d": d, "x_scale": Xq.scale,
                      "y_scale": yq.scale}
        return data, n, consts

    def stream_consts(self, stream):
        """Out-of-core constants: the quantized paths derive their
        per-feature / label scales from one-pass host statistics over
        the *whole* stream, so every rotation window quantizes on the
        same grid the resident path would."""
        n, d = stream.n_rows, stream.n_features
        if self.precision == "fp32":
            return {"n": n, "d": d}
        bits = {"int16": 16, "int8": 8}[self.precision]
        return {"n": n, "d": d,
                "x_scale": qz.symmetric_scale(stream.feature_absmax(),
                                              bits),
                "y_scale": qz.symmetric_scale(stream.label_absmax(), 16)}

    def stream_transform(self, consts, X_rows, y_rows):
        # numpy mirror of quantize_fixed_scale: this runs on the
        # Prefetcher worker thread, which must stay JAX-free (a JAX
        # dispatch there serializes behind the compiled scan) — and the
        # staged window ships int8/int16 bytes over H2D, not float32
        if self.precision == "fp32":
            return X_rows, y_rows
        bits = {"int16": 16, "int8": 8}[self.precision]
        return (qz.quantize_fixed_scale_np(X_rows, consts["x_scale"],
                                           bits),
                qz.quantize_fixed_scale_np(y_rows, consts["y_scale"],
                                           16))

    def init_state(self, consts):
        return jnp.zeros((consts["d"],), jnp.float32)

    def local_step(self, consts, w, sl):
        if self.precision == "fp32":
            r = (sl["X"] @ w - sl["y0"]) * sl["w"]          # mask padding
            g = sl["X"].T @ r
            loss = jnp.sum(r * r)
            return {"g": g, "loss": loss}
        # The weight vector is (re)quantized each step inside the local
        # step, so the resident data stays integer-only and every
        # multiply is narrow with int32 accumulation (the paper's hybrid
        # precision).  The per-feature data scale is folded INTO the
        # weight before quantizing
        # (pred_r = Σ_k Xq[r,k]·s_k·w_k = Σ_k Xq[r,k]·(s·w)q[k]),
        # so the forward dot stays purely integer.
        x_scale = consts["x_scale"]   # (1, d) broadcast against features
        wq = qz.quantize_symmetric(w * x_scale[0], bits=16)
        Xi = sl["X"]
        # (R,d)i @ (d,1)i -> (R,) — int8-limb dots on the fxp_matmul
        # Pallas kernel, int32 accumulate
        acc = dispatch.hybrid_matmul(Xi, wq.values[:, None])[:, 0]
        pred = acc * wq.scale
        yf = sl["y0"].astype(jnp.float32) * consts["y_scale"]
        r = (pred - yf) * sl["w"]
        # gradient: g_k = s_k · Σ_r Xq[r,k]·rq[r] — per-feature scale
        # factors out per output element, so the fixup is rank-1.
        rq = qz.quantize_symmetric(r, bits=16)
        gacc = dispatch.hybrid_matmul(Xi.T, rq.values[:, None])[:, 0]
        g = gacc * (x_scale[0] * rq.scale)
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
        """Serving forward pass.  fp32 is bit-exact with the
        :func:`linreg_predict` ``eval`` uses; the quantized paths run
        ``local_step``'s forward recipe (per-feature dataset scales,
        data scale folded into the 16-bit requantized weight, integer
        dot on ``fxp_matmul``).  Pad-invariant: zero rows never move a
        per-feature absmax."""
        X = jnp.asarray(X)
        if self.precision == "fp32":
            return linreg_predict(state, X)
        bits = {"int16": 16, "int8": 8}[self.precision]
        Xq = qz.quantize_symmetric(X, bits=bits, axis=0)
        wq = qz.quantize_symmetric(state * Xq.scale[0], bits=16)
        acc = dispatch.hybrid_matmul(Xq.values, wq.values[:, None])[:, 0]
        return acc * wq.scale


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
