"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before first
jax init, and smoke tests/benches must keep seeing 1 device.

Topology (TPU v5e target):
  single pod:  (16, 16)   axes ("data", "model") — 256 chips
  multi-pod:   (2, 16, 16) axes ("pod", "data", "model") — 512 chips;
               the ``pod`` axis crosses DCN (the paper's "host hop").
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the engine places its
    own data and asks only for ``shard_map`` and sharding constraints,
    which ``Explicit`` axes (the default since JAX 0.9) refuse."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (fake or real) local devices exist —
    used by tests that exercise sharded code paths on CPU."""
    return _auto_mesh((data, model), ("data", "model"))


def make_pim_mesh(pods: int = 1, data: int | None = None):
    """The PIM engine's data mesh: axes ``("pod", "data")`` — the layout
    ``PimGrid`` shards its vDPU axis over (``core.pim.make_mesh_grid``).

    ``pod`` is the slow "host hop" (DCN between pods; the compressible
    axis), ``data`` the fast ICI axis inside a pod.  ``data=None`` takes
    every local device not consumed by ``pods``, so the same call works
    on 1 real CPU device and under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8``.
    """
    n = len(jax.devices())
    if n % pods:
        raise ValueError(
            f"pods={pods} does not divide the {n} available devices")
    if data is None:
        data = n // pods
    return _auto_mesh((pods, data), ("pod", "data"))
