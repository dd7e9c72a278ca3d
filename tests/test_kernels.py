"""Pallas kernel validation: shape/dtype sweeps, interpret mode vs the
pure-jnp oracles in kernels/ref.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.kmeans_assign import kmeans_assign
from repro.kernels.split_hist import split_hist
from repro.core import lut as lutm

KEY = jax.random.PRNGKey(0)


class TestFlashAttention:
    @pytest.mark.parametrize("B,H,Kh,S,D", [
        (1, 2, 2, 128, 64),       # MHA
        (2, 4, 2, 256, 64),       # GQA 2:1
        (1, 8, 1, 128, 128),      # MQA
    ])
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref(self, B, H, Kh, S, D, causal):
        q = jax.random.normal(KEY, (B, H, S, D), jnp.float32)
        k = jax.random.normal(jax.random.fold_in(KEY, 1), (B, Kh, S, D))
        v = jax.random.normal(jax.random.fold_in(KEY, 2), (B, Kh, S, D))
        out = ops.flash_attention(q, k, v, causal=causal, block_q=64,
                                  block_k=64)
        want = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_block_size_invariance(self):
        q = jax.random.normal(KEY, (1, 2, 256, 64))
        k = jax.random.normal(jax.random.fold_in(KEY, 3), (1, 2, 256, 64))
        v = jax.random.normal(jax.random.fold_in(KEY, 4), (1, 2, 256, 64))
        o1 = ops.flash_attention(q, k, v, block_q=64, block_k=64)
        o2 = ops.flash_attention(q, k, v, block_q=128, block_k=256)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=2e-5, rtol=2e-5)

    def test_bf16(self):
        q = jax.random.normal(KEY, (1, 2, 128, 64)).astype(jnp.bfloat16)
        k = jax.random.normal(jax.random.fold_in(KEY, 5),
                              (1, 2, 128, 64)).astype(jnp.bfloat16)
        v = jax.random.normal(jax.random.fold_in(KEY, 6),
                              (1, 2, 128, 64)).astype(jnp.bfloat16)
        out = ops.flash_attention(q, k, v, block_q=64, block_k=64)
        want = ref.flash_attention_ref(q, k, v)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            atol=3e-2, rtol=3e-2)


class TestLutActivation:
    @pytest.mark.parametrize("entries", [256, 1024])
    @pytest.mark.parametrize("shape", [(256, 512), (128, 1024)])
    def test_matches_ref(self, entries, shape):
        t = lutm.sigmoid_lut(entries)
        x = jax.random.normal(KEY, shape, jnp.float32) * 4
        out = ops.lut_activation(x, t.table, x_min=t.x_min, x_max=t.x_max)
        want = ref.lut_activation_ref(x, t.table, t.x_min, t.x_max)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))

    def test_matches_framework_lut(self):
        t = lutm.gelu_lut(512)
        x = jax.random.normal(KEY, (256, 512)) * 3
        out = ops.lut_activation(x, t.table, x_min=t.x_min, x_max=t.x_max)
        want = lutm.lut_lookup(t, x)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=1e-6)


class TestFxpMatmul:
    @pytest.mark.parametrize("M,K,N", [(128, 256, 128), (256, 512, 256),
                                       (128, 1024, 128)])
    def test_exact_int32(self, M, K, N):
        a = jax.random.randint(KEY, (M, K), -128, 128, jnp.int8)
        b = jax.random.randint(jax.random.fold_in(KEY, 7), (K, N),
                               -128, 128, jnp.int8)
        out = ops.fxp_matmul(a, b)
        want = ref.fxp_matmul_ref(a, b)
        assert out.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(out), np.asarray(want))


class TestKMeansAssign:
    @pytest.mark.parametrize("N,D,K", [(2048, 16, 8), (1024, 32, 4)])
    def test_matches_ref(self, N, D, K):
        x = jax.random.normal(KEY, (N, D), jnp.float32)
        c = jax.random.normal(jax.random.fold_in(KEY, 8), (K, D))
        s1, c1, e1 = ops.kmeans_assign(x, c)
        s2, c2, e2 = ref.kmeans_assign_ref(x, c)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   atol=1e-3, rtol=1e-4)
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
        np.testing.assert_allclose(float(e1), float(e2), rtol=1e-5)

    def test_counts_sum_to_n(self):
        x = jax.random.normal(KEY, (4096, 8))
        c = jax.random.normal(jax.random.fold_in(KEY, 9), (5, 8))
        _, counts, _ = ops.kmeans_assign(x, c)
        assert float(jnp.sum(counts)) == 4096.0

    def test_weighted_matches_ref(self):
        x = jax.random.normal(KEY, (1000, 8))
        c = jax.random.normal(jax.random.fold_in(KEY, 20), (4, 8))
        w = (jax.random.uniform(jax.random.fold_in(KEY, 21), (1000,))
             > 0.25).astype(jnp.float32)
        s1, c1, e1 = ops.kmeans_assign(x, c, w)
        s2, c2, e2 = ref.kmeans_assign_ref(x, c, w)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   atol=1e-3, rtol=1e-4)
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
        np.testing.assert_allclose(float(e1), float(e2), rtol=1e-5)

    @pytest.mark.parametrize("block_n", [4096, 1024, 384])
    def test_vector_sse_accumulator_matches_ref(self, block_n):
        """The (1, 1) SSE accumulator sums across every grid step — one
        block, several, and a ragged zero-weight-padded tail."""
        x = jax.random.normal(KEY, (4096, 16), jnp.float32)
        c = jax.random.normal(jax.random.fold_in(KEY, 22), (8, 16))
        w = (jax.random.uniform(jax.random.fold_in(KEY, 23), (4096,))
             > 0.2).astype(jnp.float32)
        s1, c1, e1 = kmeans_assign(x, c, w, block_n=block_n,
                                   interpret=True)
        s2, c2, e2 = ref.kmeans_assign_ref(x, c, w)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                                   atol=1e-3, rtol=1e-4)
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
        np.testing.assert_allclose(float(e1), float(e2), rtol=1e-5)


class TestSplitHist:
    @pytest.mark.parametrize("N,F,nodes,bins,classes", [
        (1024, 8, 4, 16, 3), (512, 4, 2, 8, 2)])
    def test_matches_ref(self, N, F, nodes, bins, classes):
        node = jax.random.randint(KEY, (N,), 0, nodes)
        xb = jax.random.randint(jax.random.fold_in(KEY, 10), (N, F), 0,
                                bins)
        y = jax.random.randint(jax.random.fold_in(KEY, 11), (N,), 0,
                               classes)
        h1 = ops.split_hist(node, xb, y, n_nodes=nodes, n_bins=bins,
                            n_classes=classes)
        h2 = ref.split_hist_ref(node, xb, y, nodes, bins, classes)
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))

    def test_total_count_conserved(self):
        N = 512
        node = jax.random.randint(KEY, (N,), 0, 4)
        xb = jax.random.randint(jax.random.fold_in(KEY, 12), (N, 4), 0, 8)
        y = jax.random.randint(jax.random.fold_in(KEY, 13), (N,), 0, 2)
        h = ops.split_hist(node, xb, y, n_nodes=4, n_bins=8, n_classes=2)
        # every feature column sees every row exactly once
        np.testing.assert_allclose(np.asarray(h).sum(axis=(0, 2, 3)),
                                   N * np.ones(4))

    def test_weighted_matches_ref(self):
        N = 300                                  # non-block-aligned too
        node = jax.random.randint(KEY, (N,), 0, 4)
        xb = jax.random.randint(jax.random.fold_in(KEY, 14), (N, 3), 0, 8)
        y = jax.random.randint(jax.random.fold_in(KEY, 15), (N,), 0, 2)
        w = (jax.random.uniform(jax.random.fold_in(KEY, 16), (N,))
             > 0.5).astype(jnp.float32)
        h1 = ops.split_hist(node, xb, y, w, n_nodes=4, n_bins=8,
                            n_classes=2)
        h2 = ref.split_hist_ref(node, xb, y, 4, 8, 2, w)
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))

    @pytest.mark.parametrize("block_n", [2048, 128])
    def test_depth6_level_matches_ref(self, block_n):
        """The deepest level of a depth-6 tree (32 nodes x 32 bins x 4
        classes, 16 features) over one vDPU's 2,048 rows, in one block
        and in the TPU heuristic's 128-row blocks."""
        N, F, nodes, bins, classes = 2048, 16, 32, 32, 4
        node = jax.random.randint(KEY, (N,), 0, nodes)
        xb = jax.random.randint(jax.random.fold_in(KEY, 17), (N, F), 0,
                                bins)
        y = jax.random.randint(jax.random.fold_in(KEY, 18), (N,), 0,
                               classes)
        w = (jax.random.uniform(jax.random.fold_in(KEY, 19), (N,))
             > 0.1).astype(jnp.float32)
        h1 = split_hist(node, xb, y, w, n_nodes=nodes, n_bins=bins,
                        n_classes=classes, block_n=block_n,
                        interpret=True)
        h2 = ref.split_hist_ref(node, xb, y, nodes, bins, classes, w)
        np.testing.assert_array_equal(np.asarray(h1), np.asarray(h2))
