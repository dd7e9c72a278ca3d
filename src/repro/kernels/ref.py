"""Pure-jnp oracles for every Pallas kernel (the allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def flash_attention_ref(q, k, v, *, causal: bool = True):
    """q: (B,H,S,D); k/v: (B,Kh,S,D) with H % Kh == 0."""
    B, H, S, D = q.shape
    Kh = k.shape[1]
    G = H // Kh
    kx = jnp.repeat(k, G, axis=1)
    vx = jnp.repeat(v, G, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, kx,
                   preferred_element_type=jnp.float32)
    s = s / jnp.sqrt(jnp.asarray(D, jnp.float32))
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(vx.dtype), vx,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def lut_activation_ref(x, table, x_min: float, x_max: float):
    """Nearest-entry LUT lookup (paper insight I2)."""
    n = table.shape[0]
    step = (x_max - x_min) / (n - 1)
    idx = jnp.clip(jnp.round((x.astype(jnp.float32) - x_min) / step),
                   0, n - 1).astype(jnp.int32)
    return jnp.take(table, idx).astype(x.dtype)


def fxp_matmul_ref(a, b):
    """int8 (M,K) x int8 (K,N) -> int32 (M,N), MXU semantics."""
    return jax.lax.dot_general(
        a, b, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)


def kmeans_assign_ref(x, centroids, w=None):
    """x: (N,D) f32, centroids: (K,D), w: optional (N,) row weights ->
    (sums (K,D), counts (K,), sse ())."""
    hi = jax.lax.Precision.HIGHEST                      # as the kernel
    d = (jnp.sum(centroids ** 2, axis=1)[None, :]
         - 2.0 * jnp.dot(x, centroids.T, precision=hi))  # (N,K) + ||x||²
    a = jnp.argmin(d, axis=1)
    onehot = jax.nn.one_hot(a, centroids.shape[0], dtype=x.dtype)
    if w is not None:
        onehot = onehot * w[:, None]
    sums = jnp.dot(onehot.T, x, precision=hi)
    counts = jnp.sum(onehot, axis=0)
    best = jnp.take_along_axis(d, a[:, None], axis=1)[:, 0]
    contrib = best + jnp.sum(x * x, axis=1)
    sse = jnp.sum(contrib if w is None else contrib * w)
    return sums, counts, sse


def split_hist_ref(node_idx, xbin, y, n_nodes, n_bins, n_classes, w=None):
    """node_idx: (N,), xbin: (N,F) int bins, y: (N,) labels, w: optional
    (N,) row weights -> H (n_nodes, F, n_bins, n_classes) float32 counts."""
    N, F = xbin.shape
    f_idx = jnp.arange(F)
    flat = ((node_idx[:, None] * F + f_idx[None, :]) * n_bins
            + xbin) * n_classes + y[:, None]
    H = jnp.zeros((n_nodes * F * n_bins * n_classes,), jnp.float32)
    inc = (jnp.ones((N,), jnp.float32) if w is None
           else w.astype(jnp.float32))
    H = H.at[flat.reshape(-1)].add(
        jnp.broadcast_to(inc[:, None], (N, F)).reshape(-1))
    return H.reshape(n_nodes, F, n_bins, n_classes)
