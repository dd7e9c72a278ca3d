"""Mosaic lowering of the four Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology
that is described, not attached, and raises what the chip's compiler
would raise (unsupported operand types, scalar VMEM stores, scoped-VMEM
overflows).  Each test compiles one kernel with ``interpret=False`` at
the per-vDPU shapes of the training path — 4,096 rows a lane — with the
TPU block shapes from ``tuning.autotune``, and finds the kernel
(``tpu_custom_call``) in the compiled program.  The quantized matvecs go
through ``dispatch.hybrid_matmul`` itself, so the limbs and orientation
compiled here are the ones the training step sends.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import lut as lut_mod
from repro.kernels import dispatch
from repro.kernels import kmeans_assign as _km
from repro.kernels import lut_activation as _lut
from repro.kernels import split_hist as _sh
from repro.tuning import autotune as at

ROWS, FEATURES = 4096, 32          # one vDPU's resident logreg block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    # a described-chip compile is written to the persistent cache but
    # cannot be read back without a chip
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, *avals):
    return jax.jit(fn).lower(*avals).compile().as_text()


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("data", [jnp.int8, jnp.int16],
                         ids=["int8", "int16"])
@pytest.mark.parametrize("gradient", [False, True],
                         ids=["forward", "gradient"])
def test_fxp_matmul_int8_limbs(one_chip, no_persistent_cache, gradient,
                               data):
    """The quantized linear models' matvecs, through the dispatch call
    the training step makes: forward ``X @ w`` and gradient ``X^T r``,
    the vector a 16-bit quantized column.  ``hybrid_matmul`` picks the
    limbs, the orientation and the block shapes."""
    a_shape = (FEATURES, ROWS) if gradient else (ROWS, FEATURES)

    def matvec(a, v):
        return dispatch.hybrid_matmul(a, v[:, None], backend="tpu")

    text = _compile(matvec, _spec(one_chip, a_shape, data),
                    _spec(one_chip, (a_shape[1],), jnp.int16))
    assert "tpu_custom_call" in text


def test_kmeans_assign(one_chip, no_persistent_cache):
    N, D, K = ROWS, 16, 8
    blocks = at.block_shapes("kmeans_assign", jnp.float32, (N, D, K),
                             backend="tpu")

    def assign(x, c, w):
        return _km.kmeans_assign(x, c, w, interpret=False, **blocks)

    text = _compile(assign, _spec(one_chip, (N, D), jnp.float32),
                    _spec(one_chip, (K, D), jnp.float32),
                    _spec(one_chip, (N,), jnp.float32))
    assert "tpu_custom_call" in text


def test_split_hist_depth6(one_chip, no_persistent_cache):
    """The deepest level of a depth-6 tree: 32 nodes x 32 bins x 4
    classes over 16 features."""
    N, F = ROWS, 16
    n_nodes, n_bins, n_classes = 32, 32, 4
    blocks = at.block_shapes("split_hist", jnp.float32,
                             (N, F, n_nodes * n_bins * n_classes),
                             backend="tpu")

    def hist(node, xbin, y, w):
        return _sh.split_hist(node, xbin, y, w, n_nodes=n_nodes,
                              n_bins=n_bins, n_classes=n_classes,
                              interpret=False, **blocks)

    text = _compile(hist, _spec(one_chip, (N,), jnp.int32),
                    _spec(one_chip, (N, F), jnp.int32),
                    _spec(one_chip, (N,), jnp.int32),
                    _spec(one_chip, (N,), jnp.float32))
    assert "tpu_custom_call" in text


def test_lut_activation_logits(one_chip, no_persistent_cache):
    """The LUT sigmoid over one vDPU's logit vector."""
    table = lut_mod.sigmoid_lut(n_entries=1024)

    def sigmoid(z):
        return _lut.lut_activation(z, table.table, x_min=table.x_min,
                                   x_max=table.x_max, interpret=False)

    text = _compile(sigmoid, _spec(one_chip, (ROWS,), jnp.float32))
    assert "tpu_custom_call" in text
