"""Distribution substrate on a 1-device mesh: collectives semantics,
compression error bounds, overlap engine equivalence, sharding tables,
and the roofline HLO parser against a known module."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_host_mesh
from repro.distributed import collectives as coll
from repro.distributed import compression as comp
from repro.distributed.overlap import microbatched_grads
from repro.distributed.sharding import (LogicalRules, make_rules,
                                        shard_hint, use_rules)
from repro.launch import shardings as sh
from repro.roofline import analysis as ra


MESH = make_host_mesh(1, 1)


def _in_mesh(fn, *args):
    return shard_map(fn, mesh=MESH, in_specs=P(), out_specs=P(),
                     check_vma=False)(*args)


class TestCollectives:
    def test_quantized_psum_identity_single_shard(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (64,))
        out = _in_mesh(lambda v: coll.quantized_psum(v, "data", bits=8), x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(x),
                                   atol=float(jnp.max(jnp.abs(x))) / 100)

    def test_quantized_psum_ef_residual(self):
        x = jax.random.normal(jax.random.PRNGKey(1), (64,))
        e0 = jnp.zeros_like(x)

        def body(v, e):
            return coll.quantized_psum_ef(v, e, "data", bits=8)

        out, err = shard_map(body, mesh=MESH, in_specs=(P(), P()),
                             out_specs=(P(), P()), check_vma=False)(x, e0)
        np.testing.assert_allclose(np.asarray(out + err), np.asarray(x),
                                   atol=1e-6)

    def test_hierarchical_psum_single(self):
        x = jnp.ones((4,))
        out = _in_mesh(
            lambda v: coll.hierarchical_psum(v, ["data"], None), x)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x))


class TestCompression:
    def test_compressed_reduce_exact_axis(self):
        grads = {"a": jnp.ones((8,)), "b": jnp.full((3,), -2.0)}
        err = comp.init_error_state(grads)
        cfg = comp.CompressionConfig(slow_axis=None, fast_axes=("data",))

        def body(g, e):
            return comp.compressed_reduce(g, e, cfg)

        specs = jax.tree.map(lambda _: P(), grads)
        out, _ = shard_map(body, mesh=MESH, in_specs=(specs, specs),
                           out_specs=(specs, specs),
                           check_vma=False)(grads, err)
        np.testing.assert_array_equal(np.asarray(out["a"]), np.ones(8))

    def test_topk_sparsify(self):
        g = jnp.asarray([0.1, -5.0, 0.2, 3.0])
        kept, err = comp.topk_sparsify(g, 0.5, jnp.zeros_like(g))
        assert float(kept[1]) == -5.0 and float(kept[3]) == 3.0
        assert float(kept[0]) == 0.0
        np.testing.assert_allclose(np.asarray(kept + err), np.asarray(g))


class TestOverlap:
    def test_microbatched_equals_full_batch(self):
        key = jax.random.PRNGKey(0)
        params = {"w": jax.random.normal(key, (4,))}
        batch = {"x": jax.random.normal(key, (8, 4)),
                 "y": jax.random.normal(key, (8,))}

        def loss_fn(p, b):
            r = b["x"] @ p["w"] - b["y"]
            return jnp.mean(r ** 2), {}

        loss_f, grads_f = jax.value_and_grad(
            lambda p: loss_fn(p, batch)[0])(params), None
        full_loss, full_grads = loss_f[0], jax.grad(
            lambda p: loss_fn(p, batch)[0])(params)
        mb_loss, mb_grads, _ = microbatched_grads(
            loss_fn, params, batch, n_micro=4)
        np.testing.assert_allclose(float(mb_loss), float(full_loss),
                                   rtol=1e-5)
        np.testing.assert_allclose(np.asarray(mb_grads["w"]),
                                   np.asarray(full_grads["w"]), rtol=1e-4)


class TestShardingTables:
    def test_rules_dedup_mesh_axes(self):
        rules = make_rules(MESH, n_heads=4, n_kv_heads=2)
        spec = rules.spec("batch", "kv_seq", "kv_heads")
        # "model" claimed once: kv_seq wins, kv_heads replicated
        assert spec[1] == "model" and spec[2] is None

    def test_head_divisibility_fallback(self):
        rules = make_rules(MESH, n_heads=14, n_kv_heads=2)
        # model axis size 1 -> everything divisible; with size-16 mesh the
        # table computed at make_rules time drops heads for 14H
        from repro.distributed.sharding import make_rules as mk
        # emulate a 16-wide model axis table decision
        assert rules.table["heads"] in ("model", None)

    def test_shard_hint_truncates_extra_logical_axes(self):
        """A hint naming more logical axes than the array has dims must
        drop the extras, not pass an over-long PartitionSpec to
        with_sharding_constraint (which rejects any spec longer than
        the array's rank, even on a 1-device mesh) — the decode paths
        hint 4 axes onto arrays that are 2-D/3-D in some shapes."""
        rules = make_rules(MESH, n_heads=4, n_kv_heads=2)
        x = jnp.zeros((4, 8))
        with use_rules(rules):
            y = shard_hint(x, "batch", "seq", "heads", "head_dim")
        assert y.shape == x.shape

    def test_shard_hint_nondivisible_dim_replicates(self):
        rules = make_rules(MESH, n_heads=4, n_kv_heads=2)
        # odd dims can't split over any >1 mesh axis; on the 1-wide
        # host mesh everything divides — the contract is "no crash,
        # shape preserved" either way
        with use_rules(rules):
            y = shard_hint(jnp.zeros((3, 5, 7)), "batch", "seq", "ff")
        assert y.shape == (3, 5, 7)

    def test_param_axes_mapping(self):
        import jax.tree_util as jtu
        tree = {"stack": {"scan": ({"mixer": {
            "wq": jax.ShapeDtypeStruct((2, 8, 4, 2), jnp.float32)}},)}}
        flat, _ = jtu.tree_flatten_with_path(tree)
        axes = sh.param_axes(*flat[0])
        assert axes == (None, "embed", "heads", None)

    def test_moe_param_axes(self):
        import jax.tree_util as jtu
        tree = {"moe": {"w_gate": jax.ShapeDtypeStruct((4, 8, 16),
                                                       jnp.float32)}}
        flat, _ = jtu.tree_flatten_with_path(tree)
        assert sh.param_axes(*flat[0]) == ("experts", "embed", "ff")


class TestRooflineParser:
    def test_counts_scanned_dots(self):
        L, M, K, N = 5, 8, 16, 8

        def step(x, ws):
            def body(c, w):
                return jnp.tanh(c @ w), None
            y, _ = jax.lax.scan(body, x, ws)
            return jnp.sum(y)

        ws = jnp.zeros((L, K, K), jnp.float32)
        x = jnp.zeros((M, K), jnp.float32)
        comp = jax.jit(step).lower(x, ws).compile()
        parsed = ra.analyze_hlo(comp.as_text())
        want = 2.0 * M * K * K * L
        assert parsed.dot_flops == pytest.approx(want, rel=0.01)
        assert L in parsed.while_trips.values()

    def test_shape_bytes(self):
        assert ra._shape_bytes("bf16[4,8]{1,0}") == 64
        assert ra._shape_bytes("(f32[2,2]{1,0}, s8[4]{0})") == 20

    def test_model_flops_sane(self):
        from repro.configs import get_config
        cfg = get_config("qwen2-0.5b")
        mf = ra.model_flops(cfg, "train", 256, 4096)
        # 6 * ~0.36B active * 1M tokens ~ 2.2e15
        assert 1e15 < mf["param_flops"] < 4e15
        assert mf["n_active_params"] < mf["n_total_params"]
