"""Jitted public wrappers for the Pallas kernels.

Off the TPU every kernel runs with ``interpret=True`` — the kernel body
executes as plain JAX per grid step, validating logic against
``ref.py``; on the TPU the same calls lower to Mosaic.
``interpret_mode()`` decides at trace time, so importing this module
touches no backend.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import lut_activation as _lut
from repro.kernels import fxp_matmul as _fxp
from repro.kernels import kmeans_assign as _km
from repro.kernels import split_hist as _sh


def interpret_mode(backend: str | None = None) -> bool:
    """True where the kernels run in Pallas interpret mode: any backend
    but the TPU (``backend`` defaults to JAX's default backend)."""
    return (backend or jax.default_backend()) != "tpu"


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                    block_k: int = 512):
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=interpret_mode())


@partial(jax.jit, static_argnames=("x_min", "x_max"))
def lut_activation(x, table, *, x_min: float, x_max: float):
    return _lut.lut_activation(x, table, x_min=x_min, x_max=x_max,
                               interpret=interpret_mode())


@jax.jit
def fxp_matmul(a, b):
    from repro.tuning import autotune as _at
    blocks = _at.block_shapes("fxp_matmul", a.dtype,
                              (a.shape[0], a.shape[1], b.shape[1]))
    return _fxp.fxp_matmul(a, b, interpret=interpret_mode(), **blocks)


@jax.jit
def kmeans_assign(x, centroids, w=None):
    from repro.tuning import autotune as _at
    blocks = _at.block_shapes(
        "kmeans_assign", x.dtype,
        (x.shape[0], x.shape[1], centroids.shape[0]))
    return _km.kmeans_assign(x, centroids, w,
                             interpret=interpret_mode(), **blocks)


@partial(jax.jit, static_argnames=("n_nodes", "n_bins", "n_classes"))
def split_hist(node_idx, xbin, y, w=None, *, n_nodes: int, n_bins: int,
               n_classes: int):
    from repro.tuning import autotune as _at
    blocks = _at.block_shapes(
        "split_hist", jnp.float32,
        (xbin.shape[0], xbin.shape[1], n_nodes * n_bins * n_classes))
    return _sh.split_hist(node_idx, xbin, y, w, n_nodes=n_nodes,
                          n_bins=n_bins, n_classes=n_classes,
                          interpret=interpret_mode(), **blocks)
