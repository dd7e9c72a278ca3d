"""The estimators' resident row format: float rows, or the paper's
hybrid-precision fixed point (insight I1).

Every estimator's ``precision`` string picks one :func:`row_format`,
and the format is the one place that knows the recipe:

  * ``fp32`` (:class:`FloatRows`) — rows stay float32 and the dots are
    plain ``jnp`` matmuls (what a CPU/GPU would run);
  * ``int16`` / ``int8`` (:class:`FixedRows`) — the *dataset copy* is
    quantized once, symmetrically, with one scale per feature (the
    ``(1, d)`` array constant ``x_scale``).  The forward dot folds that
    scale into the weight before requantizing it to 16 bits,
    ``pred_r = Σ_k Xq[r,k]·s_k·w_k = Σ_k Xq[r,k]·(s·w)q[k]``, so it runs
    integer-only on ``dispatch.hybrid_matmul`` (the ``fxp_matmul``
    Pallas kernel, int32 accumulation) and one rescale by the weight's
    scale restores float.  The gradient dot quantizes the residual to
    16 bits; the per-feature scale factors out per output element,
    ``g_k = s_k · Σ_r Xq[r,k]·rq[r]``, so its fixup is rank-1.

A format is a frozen value that :func:`row_format` picks from
``precision``, the one setting a user reaches.  What it adds to
``consts`` are arrays, the runner's operand, so two binds of the same
estimator share one runner whatever their scales.
"""

from __future__ import annotations

import dataclasses
from typing import Literal, Optional

import jax.numpy as jnp

from repro.core import quantize as qz
from repro.kernels import dispatch

Precision = Literal["fp32", "int16", "int8"]

_BITS = {"int16": 16, "int8": 8}


class _Rows:
    def request(self, X):
        """A serving batch in the resident format: ``(values,
        scales)``, as :meth:`place`, so ``predict`` reruns training's
        forward.  The quantized formats take one scale per request
        batch."""
        return self.place(X)


@dataclasses.dataclass(frozen=True)
class FloatRows(_Rows):
    """fp32 rows: every operation is the identity or a plain matmul."""

    def place(self, X):
        """``(values, scales)``: the resident rows and the ``consts``
        entries they need."""
        return X, {}

    def stream_scales(self, absmax):
        """The scales of an out-of-core fit, from ``absmax()``, a
        one-pass host statistic over the whole stream."""
        return {}

    def unit_scales(self, features: int):
        """Scales for lowering without resident data."""
        return {}

    def stage(self, consts, rows):
        """A streaming window's host rows in the resident format."""
        return rows

    def matmul(self, consts, X, W):
        """``X @ W`` for a ``(d,)`` or ``(d, c)`` float ``W``."""
        return X @ W

    def rmatmul(self, consts, X, R):
        """``X.T @ R`` for an ``(r,)`` or ``(r, c)`` float ``R``."""
        return X.T @ R

    def dequantize(self, consts, X):
        """Float rows for the float kernels (k-means)."""
        return X


FLOAT = FloatRows()


def _per_feature(scale, like):
    """The ``(d,)`` feature scale, broadcast against a ``(d,)`` or
    ``(d, c)`` operand."""
    return scale if like.ndim == 1 else scale[:, None]


def _int_dot(A, B):
    """``A @ B`` on ``hybrid_matmul`` for a ``(k,)`` or ``(k, c)``
    integer ``B``."""
    if B.ndim == 1:
        return dispatch.hybrid_matmul(A, B[:, None])[:, 0]
    return dispatch.hybrid_matmul(A, B)


@dataclasses.dataclass(frozen=True)
class FixedRows(_Rows):
    """Symmetric ``bits``-bit rows with a scale under ``consts[key]``:
    one per feature (``axis=0``, shape ``(1, d)``), or one for the whole
    tensor (``axis=None``, linreg's 16-bit labels)."""

    bits: int
    key: str = "x_scale"
    axis: Optional[int] = 0

    def place(self, X):
        q = qz.quantize_symmetric(X, bits=self.bits, axis=self.axis)
        return q.values, {self.key: q.scale}

    def stream_scales(self, absmax):
        # the scale quantize_symmetric would find over the whole stream,
        # so every window quantizes on the resident grid
        return {self.key: qz.symmetric_scale(absmax(), self.bits)}

    def unit_scales(self, features: int):
        return {self.key: jnp.ones((1, features), jnp.float32)}

    def stage(self, consts, rows):
        # numpy: the Prefetcher worker stays JAX-free (a JAX dispatch
        # there serializes behind the compiled scan), and the window
        # ships int8/int16 bytes over H2D, not float32
        return qz.quantize_fixed_scale_np(rows, consts[self.key], self.bits)

    def matmul(self, consts, X, W):
        wq = qz.quantize_symmetric(
            W * _per_feature(consts[self.key][0], W), bits=16)
        return _int_dot(X, wq.values) * wq.scale

    def rmatmul(self, consts, X, R):
        rq = qz.quantize_symmetric(R, bits=16)
        return _int_dot(X.T, rq.values) \
            * (_per_feature(consts[self.key][0], R) * rq.scale)

    def dequantize(self, consts, X):
        # the per-feature scale rides in registers (the paper's bank
        # layout); the resident copy stays integer
        return X.astype(jnp.float32) * consts[self.key]


def row_format(precision: Precision):
    """The row format of ``precision``; an unknown one raises
    ``KeyError``."""
    if precision == "fp32":
        return FLOAT
    return FixedRows(_BITS[precision])
