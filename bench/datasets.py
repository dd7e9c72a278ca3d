"""Synthetic training sets, made on the device from a key.

Copies of ``binary_classification`` and ``blobs`` from the program's
``src/repro/core/datasets.py`` (the paper's dense synthetic sets: labels
drawn from a logistic model; K gaussian blobs), kept with the benchmark
so that its traffic does not move with the program.  A configuration
names its generator by the key of :data:`GENERATORS`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def binary_classification(key, n: int, d: int, w_scale: float = 2.0):
    """Returns (X, y in {0, 1}, w_true); labels drawn from the logistic
    model."""
    kx, kw, kb = jax.random.split(key, 3)
    X = jax.random.normal(kx, (n, d), jnp.float32)
    w = jax.random.normal(kw, (d,), jnp.float32) * w_scale / jnp.sqrt(d)
    p = jax.nn.sigmoid(X @ w)
    y = (jax.random.uniform(kb, (n,)) < p).astype(jnp.float32)
    return X, y, w


def blobs(key, n: int, d: int, k: int, spread: float = 0.3,
          box: float = 2.0):
    """Returns (X, assignment, centers): K gaussian blobs in
    [-box, box]^d."""
    kc, ka, kn = jax.random.split(key, 3)
    centers = jax.random.uniform(kc, (k, d), jnp.float32, -box, box)
    assign = jax.random.randint(ka, (n,), 0, k)
    X = centers[assign] + spread * jax.random.normal(kn, (n, d), jnp.float32)
    return X, assign, centers


GENERATORS = {"binary_classification": binary_classification,
              "blobs": blobs}
