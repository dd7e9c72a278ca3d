"""Mosaic lowering of the four Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler compiles for a ``v5e:2x2`` topology
that is described, not attached, and raises what the chip's compiler
would raise (unsupported operand types, scalar VMEM stores, scoped-VMEM
overflows).  Each test compiles one kernel with ``interpret=False`` at
the per-vDPU shapes of the training path — 4,096 rows a lane — with the
TPU block shapes from ``tuning.autotune``, and finds the kernel
(``tpu_custom_call``) in the compiled program, named by its
``kernel_metadata`` (``"kernel":"<name>"``), which the profiler's trace
carries in each kernel event's text.  The quantized matvecs go
through ``dispatch.hybrid_matmul`` itself, so the limbs and orientation
compiled here are the ones the training step sends, and the LUT sigmoid
is compiled vmapped over the benchmark cells' 2,048 vDPUs, as the step
calls it.

The four-chip deployment's whole runner is compiled too: the int8 logistic
regression that ``api.fit`` runs on a ``(1, 4)`` mesh of the described
chips, kernels inside ``shard_map`` and the partials' all-reduce.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library at a time.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro.core import lut as lut_mod
from repro.core import make_mesh_grid
from repro.core.mlalgos import LogReg
from repro.kernels import dispatch
from repro.kernels import kmeans_assign as _km
from repro.kernels import lut_activation as _lut
from repro.kernels import split_hist as _sh
from repro.tuning import autotune as at

ROWS, FEATURES = 4096, 32          # one vDPU's resident logreg block
VDPUS = 2048                       # the logreg cells' vDPUs on one chip
GD_ROWS = 8192                     # and each vDPU's rows
CHIP_HBM_BYTES = 16 * 2 ** 30      # a v5e's memory


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
    assert '"kernel":"fxp_matmul"' in text


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
    assert '"kernel":"kmeans_assign"' in text


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
    assert '"kernel":"split_hist"' in text


@pytest.mark.parametrize("rows", [8192, 64], ids=["gd", "sgd64"])
def test_lut_activation_logits(one_chip, no_persistent_cache, rows):
    """The LUT sigmoid over every vDPU's logit vector, vmapped over the
    vDPUs as the training step does it: full-batch GD's 8,192 rows and
    a 64-row SGD batch a vDPU.  The batching rule runs the kernel once
    over the whole batch, so its name carries no ``vmap_``."""
    table = lut_mod.sigmoid_lut(n_entries=1024)

    def sigmoid(z):
        return _lut.lut_activation(z, table.table, x_min=table.x_min,
                                   x_max=table.x_max, interpret=False)

    text = _compile(jax.vmap(sigmoid),
                    _spec(one_chip, (VDPUS, rows), jnp.float32))
    kernels = [line for line in text.splitlines()
               if "tpu_custom_call" in line]
    assert len(kernels) == 1
    assert '"kernel":"lut_activation"' in text
    name = kernels[0].split("=", 1)[0]
    assert "lut_activation" in name and "vmap_" not in name


def test_mesh_logreg_runner(topo, no_persistent_cache, monkeypatch):
    """The chunk runner ``api.fit`` builds for ``LogReg(precision="int8",
    sigmoid="lut")`` on a ``(1, 4)`` mesh, at the shapes of the four-chip
    deployment ``logreg-int8-16m-x4``: 2,048 vDPUs of 8,192 rows x 32
    features a chip, sharded over
    the mesh as ``PimGrid.shard_rows`` lays them out.  Both kernels
    compile under Mosaic inside ``shard_map``, a chip's arguments and
    temporaries fit its memory, and the partials meet in an
    all-reduce."""
    # the kernels and the runner's donation ask JAX's default backend,
    # which is the CPU while a described chip compiles
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    chips = len(topo.devices)
    mesh = Mesh(np.asarray(topo.devices).reshape(1, chips), ("pod", "data"),
                axis_types=(AxisType.Auto,) * 2)
    grid = make_mesh_grid(VDPUS * chips, mesh=mesh)
    local_fn, update_fn, state0, consts = LogReg(
        precision="int8", sigmoid="lut").spec_fns(
            features=FEATURES, rows=grid.n_vdpus * GD_ROWS)
    rows = grid.data_sharding()
    shape = (grid.n_vdpus, GD_ROWS)
    data = {"X": jax.ShapeDtypeStruct(shape + (FEATURES,), jnp.int8,
                                      sharding=rows),
            "y0": jax.ShapeDtypeStruct(shape, jnp.float32, sharding=rows),
            "w": jax.ShapeDtypeStruct(shape, jnp.float32, sharding=rows)}
    state, consts = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                       sharding=grid.replicated_sharding()),
        (state0, consts))
    compiled = grid.make_runner(local_fn, update_fn).lower(
        state, data, consts, length=32).compile()    # api.fit's scan_chunk
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert '"kernel":"fxp_matmul"' in text
    assert '"kernel":"lut_activation"' in text
    mem = compiled.memory_analysis()
    chip_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                  - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert chip_bytes < CHIP_HBM_BYTES
    assert re.search(r" all-reduce(-start)?\(", text)
