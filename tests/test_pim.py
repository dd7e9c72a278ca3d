"""PimGrid engine semantics: the virtual-DPU map-reduce must be exactly a
sum over row shards, padding must never leak, and the shard_map (mesh)
path must agree with the single-device path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pim import PimGrid, make_cpu_grid
from repro.launch.mesh import make_host_mesh


def test_shard_rows_pads_and_masks():
    grid = make_cpu_grid(8)
    X = jnp.arange(30, dtype=jnp.float32)[:, None]
    data, n = grid.shard_rows(X)
    assert n == 30
    assert data["X"].shape == (8, 4, 1)
    assert float(jnp.sum(data["w"])) == 30.0


def test_map_reduce_equals_direct_sum():
    grid = make_cpu_grid(16)
    key = jax.random.PRNGKey(0)
    X = jax.random.normal(key, (1000, 5))
    data, n = grid.shard_rows(X)

    def local_fn(_, sl):
        return {"s": jnp.sum(sl["X"] * sl["w"][:, None], axis=0),
                "n": jnp.sum(sl["w"])}

    out = grid.map_reduce(local_fn, (), data)
    np.testing.assert_allclose(np.asarray(out["s"]),
                               np.asarray(jnp.sum(X, axis=0)), rtol=1e-5)
    assert float(out["n"]) == 1000.0


def test_vdpu_count_invariance():
    """Statistics must not depend on the grid size (paper scaling runs)."""
    key = jax.random.PRNGKey(1)
    X = jax.random.normal(key, (512, 3))

    def local_fn(_, sl):
        return jnp.sum(sl["X"] ** 2 * sl["w"][:, None])

    outs = []
    for v in (4, 16, 64):
        grid = make_cpu_grid(v)
        data, _ = grid.shard_rows(X)
        outs.append(float(grid.map_reduce(local_fn, (), data)))
    np.testing.assert_allclose(outs[0], outs[1], rtol=1e-5)
    np.testing.assert_allclose(outs[0], outs[2], rtol=1e-5)


def test_mesh_path_matches_single_device():
    mesh = make_host_mesh(1, 1)
    grid_m = PimGrid(n_vdpus=8, mesh=mesh, data_axes=("data",))
    grid_s = make_cpu_grid(8)
    X = jax.random.normal(jax.random.PRNGKey(2), (100, 4))

    def local_fn(w, sl):
        return sl["X"].T @ (sl["X"] @ w * sl["w"])

    w = jnp.ones((4,))
    d_m, _ = grid_m.shard_rows(X)
    d_s, _ = grid_s.shard_rows(X)
    out_m = grid_m.map_reduce(local_fn, w, d_m)
    out_s = grid_s.map_reduce(local_fn, w, d_s)
    np.testing.assert_allclose(np.asarray(out_m), np.asarray(out_s),
                               rtol=1e-5)


@pytest.mark.parametrize("source", ["device", "host"])
def test_mesh_shard_rows_lays_rows_out_on_the_mesh(source):
    """Device arrays are laid out by one program with mesh output
    shardings, host arrays are sliced per device; both give the same
    (padded, masked) vDPU layout as the single-device grid."""
    mesh = make_host_mesh(1, 1)
    grid_m = PimGrid(n_vdpus=8, mesh=mesh, data_axes=("data",))
    X = np.random.default_rng(3).normal(size=(100, 4)).astype(np.float32)
    y = np.arange(100, dtype=np.int32)
    src = (X, y) if source == "host" else (jnp.asarray(X), jnp.asarray(y))
    d_m, n = grid_m.shard_rows(*src)
    d_s, _ = make_cpu_grid(8).shard_rows(X, y)
    assert n == 100
    for k in ("X", "w", "y0"):
        assert d_m[k].sharding == grid_m.data_sharding()
        np.testing.assert_array_equal(np.asarray(d_m[k]),
                                      np.asarray(d_s[k]))


def test_fit_loop_runs_and_tracks_metrics():
    grid = make_cpu_grid(4)
    X = jax.random.normal(jax.random.PRNGKey(3), (64, 2))
    data, n = grid.shard_rows(X)

    def local_fn(w, sl):
        return {"g": jnp.sum(sl["X"] * sl["w"][:, None], axis=0)}

    def update_fn(w, merged):
        return w - 0.1 * merged["g"] / n, {"gnorm": jnp.sum(merged["g"]**2)}

    w, hist = grid.fit(init_state=jnp.zeros((2,)), local_fn=local_fn,
                       update_fn=update_fn, data=data, steps=5)
    assert len(hist) == 5
    assert w.shape == (2,)


def test_grid_shard_count_properties():
    grid = make_cpu_grid(8)
    assert grid.n_shards == 1
    assert grid.data_sharding() is None
    mesh = make_host_mesh(1, 1)
    grid_m = PimGrid(n_vdpus=8, mesh=mesh, data_axes=("data",))
    assert grid_m.n_shards == 1
    assert grid_m.data_sharding() is not None
