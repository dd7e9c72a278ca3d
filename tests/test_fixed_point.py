"""The hybrid-precision recipe of the quantized estimators, bit for bit.

Each case runs one estimator's ``local_step``, ``predict`` or
``stream_transform`` at ``int8`` or ``int16`` and compares it with the
recipe written out here from ``quantize``'s primitives:

* the rows are quantized symmetrically, one scale per feature;
* the forward dot folds that scale into the weight, requantizes the
  weight to 16 bits, runs one integer dot and rescales by the weight's
  scale;
* the gradient dot requantizes the residual to 16 bits, runs one
  integer dot over the transposed rows and rescales by the feature
  scale times the residual's scale;
* k-means dequantizes the rows into the float distance kernel;
* ``predict`` quantizes each request batch with its own scales;
* a streaming window quantized at the scales ``prepare`` found holds
  ``prepare``'s resident values.

The integer dot is ``quantize.hybrid_dot``: int8 limbs, int32 dots, a
float32 sum, which the ``fxp_matmul`` kernel path matches bit for bit.
A single int32 dot cannot hold an int16 x int16 sum over the features.
The tolerance tests of the quantized fits (``test_fixed_point_parity``,
``test_int8_parity``) only bound the distance to fp32; these pin the
numbers themselves.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import datasets, make_cpu_grid
from repro.core import quantize as qz
from repro.core.mlalgos import (KMeans, LinearSVM, LinReg, LogReg,
                                MultinomialLogReg)
from repro.kernels import dispatch

GRID = make_cpu_grid(4)
N, D, C, K = 256, 8, 3, 4      # rows, features, classes, clusters
REQUEST = 50                   # rows of a predict batch
BITS = {"int8": 8, "int16": 16}


def _problem(name):
    """``(estimator factory, X, y, state)`` on seeded data."""
    kd, ks = jax.random.split(jax.random.PRNGKey(18))
    if name == "linreg":
        X, y, _ = datasets.regression(kd, N, D)
        return LinReg, X, y, jax.random.normal(ks, (D,))
    if name in ("logreg", "svm"):
        X, y, _ = datasets.binary_classification(kd, N, D)
        est = LogReg if name == "logreg" else LinearSVM
        return est, X, y, jax.random.normal(ks, (D,))
    if name == "multinomial":
        X, y = datasets.mixture_classification(kd, N, D, C)
        return (lambda precision: MultinomialLogReg(
            n_classes=C, precision=precision)), X, y, \
            jax.random.normal(ks, (D, C))
    X, _, _ = datasets.blobs(kd, N, D, K)
    return (lambda precision: KMeans(k=K, precision=precision)), X, None, \
        jax.random.normal(ks, (K, D))


def _per_feature(scale, like):
    return scale[0] if like.ndim == 1 else scale[0][:, None]


def _int_dot(A, B):
    """``A @ B`` for a ``(k,)`` or ``(k, c)`` integer ``B``."""
    out = qz.hybrid_dot(A, B.reshape(B.shape[0], -1))
    return out.reshape(A.shape[:1] + B.shape[1:])


def _forward(Xq, scale, W):
    wq = qz.quantize_symmetric(W * _per_feature(scale, W), bits=16)
    return _int_dot(Xq, wq.values) * wq.scale


def _gradient(Xq, scale, R):
    rq = qz.quantize_symmetric(R, bits=16)
    return _int_dot(Xq.T, rq.values) * (_per_feature(scale, R) * rq.scale)


def _local_step(name, Xq, scale, y, state):
    """The estimator's partial statistics over the rows ``Xq`` of one
    vDPU, the first ``len(Xq)`` of the dataset."""
    rows = Xq.shape[0]
    w = jnp.ones((rows,), jnp.float32)
    if name == "kmeans":
        xf = Xq.astype(jnp.float32) * scale
        sums, counts, sse = dispatch.kmeans_partials(xf, state, w)
        return {"sums": sums, "counts": counts, "sse": sse}
    z = _forward(Xq, scale, state)
    if name == "linreg":
        yq = qz.quantize_symmetric(y, bits=16)    # one scale, all rows
        r = (z - yq.values[:rows].astype(jnp.float32) * yq.scale) * w
    elif name == "logreg":
        r = (jax.nn.sigmoid(z) - y[:rows]) * w
    elif name == "svm":
        ys = jnp.where(y[:rows] > 0, 1.0, -1.0).astype(jnp.float32)
        r = -(ys * ((ys * z < 1.0).astype(jnp.float32) * w))
    else:
        onehot = jax.nn.one_hot(y[:rows], C, dtype=jnp.float32)
        r = (jax.nn.softmax(z, axis=-1) - onehot) * w[:, None]
    return {"g": _gradient(Xq, scale, r)}


def _predict(name, X, bits, state):
    q = qz.quantize_symmetric(X, bits=bits, axis=0)
    if name == "kmeans":
        xf = q.values.astype(jnp.float32) * q.scale
        return dispatch.nearest_centroid(xf, state)
    z = _forward(q.values, q.scale, state)
    if name == "logreg":
        return jax.nn.sigmoid(z)
    if name == "multinomial":
        return jax.nn.softmax(z, axis=-1)
    return z


LINEAR = ("linreg", "logreg", "svm", "multinomial")
CASES = ([(n, p, "local_step") for n in LINEAR + ("kmeans",)
          for p in BITS]
         + [(n, p, "predict") for n in LINEAR + ("kmeans",) for p in BITS]
         + [(n, p, "stream") for n in LINEAR + ("kmeans",) for p in BITS])


@pytest.mark.parametrize("name,precision,what", CASES)
def test_matches_written_out_recipe(name, precision, what):
    make, X, y, state = _problem(name)
    est = make(precision=precision)
    data, n, consts = est.prepare(GRID, X, y)
    q = qz.quantize_symmetric(X, bits=BITS[precision], axis=0)
    # the resident copy is the recipe's, on the recipe's scales
    np.testing.assert_array_equal(np.asarray(consts["x_scale"]),
                                  np.asarray(q.scale))
    if what == "local_step":
        rows = N // GRID.n_vdpus          # vDPU 0 holds rows [0, rows)
        sl = {k: v[0] for k, v in data.items()}
        got = est.local_step(consts, state, sl)
        want = _local_step(name, q.values[:rows], q.scale, y, state)
        for key, value in want.items():
            np.testing.assert_array_equal(np.asarray(got[key]),
                                          np.asarray(value), err_msg=key)
    elif what == "predict":
        Xr = X[N - REQUEST:]              # its own per-feature absmax
        np.testing.assert_array_equal(
            np.asarray(est.predict(state, Xr)),
            np.asarray(_predict(name, Xr, BITS[precision], state)))
    else:
        staged = est.stream_transform(
            consts, np.asarray(X), None if y is None else np.asarray(y))
        resident = [data["X"]] + ([data["y0"]] if "y0" in data else [])
        assert len(staged) == len(resident)
        for got, want in zip(staged, resident):
            assert got.dtype == want.dtype
            np.testing.assert_array_equal(
                got, np.asarray(want).reshape(got.shape))
