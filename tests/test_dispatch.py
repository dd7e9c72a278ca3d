"""Kernel-dispatch layer: Pallas (interpret) vs pure-jnp reference parity
for every mlalgo hot spot, plus the block-padding regression tests."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import datasets, lut as lutm, make_cpu_grid
from repro.core import quantize as qz
from repro.core.mlalgos import (train_linreg, train_logreg, train_kmeans,
                                train_dtree)
from repro.core.mlalgos.dtree import dtree_predict
from repro.kernels import dispatch, ops, ref
from repro.kernels.fxp_matmul import fxp_matmul

KEY = jax.random.PRNGKey(0)


class TestFxpPadding:
    @pytest.mark.parametrize("M,K,N", [(300, 130, 70), (1, 1, 1),
                                       (257, 513, 129)])
    def test_non_aligned_shapes(self, M, K, N):
        """Regression: the seed hard-asserted block divisibility."""
        a = jax.random.randint(KEY, (M, K), -128, 128, jnp.int8)
        b = jax.random.randint(jax.random.fold_in(KEY, 1), (K, N),
                               -128, 128, jnp.int8)
        out = fxp_matmul(a, b, interpret=True)
        want = ref.fxp_matmul_ref(a, b)
        assert out.shape == (M, N) and out.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    def test_non_aligned_via_ops(self):
        a = jax.random.randint(KEY, (300, 130), -128, 128, jnp.int8)
        b = jax.random.randint(jax.random.fold_in(KEY, 2), (130, 70),
                               -128, 128, jnp.int8)
        np.testing.assert_array_equal(np.asarray(ops.fxp_matmul(a, b)),
                                      np.asarray(ref.fxp_matmul_ref(a, b)))


class TestHybridMatmul:
    @pytest.mark.parametrize("adt,bdt", [(jnp.int8, jnp.int8),
                                         (jnp.int8, jnp.int16),
                                         (jnp.int16, jnp.int16)])
    def test_matches_hybrid_dot(self, adt, bdt):
        lim_a = 128 if adt == jnp.int8 else 32768
        lim_b = 128 if bdt == jnp.int8 else 32768
        a = jax.random.randint(KEY, (37, 19), -lim_a, lim_a).astype(adt)
        b = jax.random.randint(jax.random.fold_in(KEY, 3), (19, 5),
                               -lim_b, lim_b).astype(bdt)
        out = dispatch.hybrid_matmul(a, b)
        want = qz.hybrid_dot(a, b)
        assert out.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    @pytest.mark.parametrize("M,N", [(37, 5), (300, 1)],
                             ids=["wide", "tall-matvec"])
    @pytest.mark.parametrize("adt,bdt", [(jnp.int8, jnp.int8),
                                         (jnp.int8, jnp.int16),
                                         (jnp.int16, jnp.int16)])
    def test_extreme_values_bit_identical(self, adt, bdt, M, N):
        """Full-range operands, both ends included (int16's top values
        take the third signed limb), in both the plain and the
        transposed (tall, narrow) orientation."""
        K = 19
        rng = np.random.default_rng(7)

        def draw(shape, dt):
            info = np.iinfo(dt)
            v = rng.integers(info.min, info.max + 1, shape).astype(dt)
            v.flat[:2] = (info.min, info.max)
            return jnp.asarray(v)

        a, b = draw((M, K), adt), draw((K, N), bdt)
        out = dispatch.hybrid_matmul(a, b)
        want = qz.hybrid_dot(a, b)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))
        exact = np.asarray(a, np.int64) @ np.asarray(b, np.int64)
        np.testing.assert_allclose(np.asarray(out, np.float64), exact,
                                   rtol=1e-6)

    def test_k_chunking_matches_hybrid_dot(self):
        """K larger than k_chunk must chunk (overflow guard parity)."""
        a = jax.random.randint(KEY, (8, 100), -32768, 32767
                               ).astype(jnp.int16)
        b = jax.random.randint(jax.random.fold_in(KEY, 11), (100, 2),
                               -32768, 32767).astype(jnp.int16)
        out = dispatch.hybrid_matmul(a, b, k_chunk=32)
        want = qz.hybrid_dot(a, b, k_chunk=32)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    def test_vmapped_as_in_local_fn(self):
        """map_reduce vmaps local_fn over the vDPU axis — the Pallas path
        must batch."""
        a = jax.random.randint(KEY, (4, 33, 7), -128, 128, jnp.int8)
        b = jax.random.randint(jax.random.fold_in(KEY, 4), (7, 1),
                               -32768, 32767).astype(jnp.int16)
        out = jax.vmap(lambda x: dispatch.hybrid_matmul(x, b))(a)
        want = jax.vmap(lambda x: qz.hybrid_dot(x, b))(a)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


class TestWeightedKernels:
    def test_kmeans_partials_masks_padding(self):
        x = jax.random.normal(KEY, (200, 6))
        c = jax.random.normal(jax.random.fold_in(KEY, 5), (4, 6))
        w = (jax.random.uniform(jax.random.fold_in(KEY, 6), (200,))
             > 0.3).astype(jnp.float32)
        s1, c1, e1 = dispatch.kmeans_partials(x, c, w)
        s2, c2, e2 = ref.kmeans_assign_ref(x, c, w)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   atol=1e-4, rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
        np.testing.assert_allclose(float(e1), float(e2), rtol=1e-5)

    def test_level_histogram_masks_padding(self):
        N, F, nodes, bins, classes = 333, 5, 4, 8, 3
        node = jax.random.randint(KEY, (N,), 0, nodes)
        xb = jax.random.randint(jax.random.fold_in(KEY, 7), (N, F), 0,
                                bins)
        y = jax.random.randint(jax.random.fold_in(KEY, 8), (N,), 0,
                               classes)
        w = (jax.random.uniform(jax.random.fold_in(KEY, 9), (N,))
             > 0.5).astype(jnp.float32)
        h1 = dispatch.level_histogram(node, xb, y, w, n_nodes=nodes,
                                      n_bins=bins, n_classes=classes)
        h2 = ref.split_hist_ref(node, xb, y, nodes, bins, classes, w)
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
        # weighted total: each feature column sums to Σw
        np.testing.assert_allclose(np.asarray(h1).sum(axis=(0, 2, 3)),
                                   float(jnp.sum(w)) * np.ones(F))

    def test_lut_apply_matches_lookup(self):
        t = lutm.sigmoid_lut(512)
        x = jax.random.normal(KEY, (123,)) * 5         # odd 1D shape
        out = dispatch.lut_apply(t, x)
        want = lutm.lut_lookup(t, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-6)


class TestUseKernelsToggle:
    def test_flag_flips_and_restores(self):
        assert dispatch.kernels_enabled()
        with dispatch.use_kernels(False):
            assert not dispatch.kernels_enabled()
        assert dispatch.kernels_enabled()

    def test_reference_path_matches_kernel_path(self):
        a = jax.random.randint(KEY, (40, 12), -32768, 32767
                               ).astype(jnp.int16)
        b = jax.random.randint(jax.random.fold_in(KEY, 10), (12, 3),
                               -32768, 32767).astype(jnp.int16)
        out_k = dispatch.hybrid_matmul(a, b)
        with dispatch.use_kernels(False):
            out_r = dispatch.hybrid_matmul(a, b)
        np.testing.assert_array_equal(np.asarray(out_k), np.asarray(out_r))


class TestEndToEndParity:
    """Each mlalgo trained through the Pallas dispatch must match the
    pure-jnp reference path (the paper's accuracy-parity claim, now at
    the kernel boundary)."""

    def test_linreg_int8(self):
        X, y, _ = datasets.regression(KEY, 600, 8)
        grid = make_cpu_grid(8)
        r_k = train_linreg(grid, X, y, lr=0.05, steps=40,
                           precision="int8")
        with dispatch.use_kernels(False):
            r_r = train_linreg(grid, X, y, lr=0.05, steps=40,
                               precision="int8")
        np.testing.assert_allclose(np.asarray(r_k.w), np.asarray(r_r.w),
                                   atol=1e-5, rtol=1e-5)

    def test_logreg_int16_lut(self):
        X, y, _ = datasets.binary_classification(KEY, 600, 6)
        grid = make_cpu_grid(8)
        r_k = train_logreg(grid, X, y, lr=0.5, steps=40,
                           precision="int16", sigmoid="lut")
        with dispatch.use_kernels(False):
            r_r = train_logreg(grid, X, y, lr=0.5, steps=40,
                               precision="int16", sigmoid="lut")
        np.testing.assert_allclose(np.asarray(r_k.w), np.asarray(r_r.w),
                                   atol=1e-5, rtol=1e-5)

    def test_kmeans_int8(self):
        X, _, _ = datasets.blobs(KEY, 600, 4, k=3, spread=0.3)
        grid = make_cpu_grid(8)
        r_k = train_kmeans(grid, X, 3, iters=8, precision="int8")
        with dispatch.use_kernels(False):
            r_r = train_kmeans(grid, X, 3, iters=8, precision="int8")
        np.testing.assert_allclose(np.asarray(r_k.centroids),
                                   np.asarray(r_r.centroids),
                                   atol=1e-4, rtol=1e-5)

    def test_dtree_bins(self):
        X, y = datasets.mixture_classification(KEY, 600, 6, n_classes=2)
        grid = make_cpu_grid(8)
        r_k = train_dtree(grid, X, y, max_depth=3, n_bins=16,
                          n_classes=2)
        with dispatch.use_kernels(False):
            r_r = train_dtree(grid, X, y, max_depth=3, n_bins=16,
                              n_classes=2)
        np.testing.assert_array_equal(
            np.asarray(dtree_predict(r_k.tree, X)),
            np.asarray(dtree_predict(r_r.tree, X)))
        np.testing.assert_array_equal(np.asarray(r_k.tree.feature),
                                      np.asarray(r_r.tree.feature))
