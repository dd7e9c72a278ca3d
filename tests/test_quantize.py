"""Unit + property tests for fixed-point arithmetic (paper insight I1)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import quantize as qz


class TestQFormat:
    def test_roundtrip_error_bound(self):
        fmt = qz.Q3_12
        x = jnp.linspace(-7.9, 7.9, 1001)
        err = jnp.abs(fmt.dequantize(fmt.quantize(x)) - x)
        assert float(jnp.max(err)) <= 0.5 / fmt.scale + 1e-7

    def test_saturation(self):
        fmt = qz.Q1_6  # int8, range ~[-2, 2)
        q = fmt.quantize(jnp.asarray([100.0, -100.0]))
        assert int(q[0]) == 127 and int(q[1]) == -128

    def test_mul_matches_float(self):
        fmt = qz.Q3_12
        a, b = jnp.asarray(1.5), jnp.asarray(-2.25)
        prod = fmt.dequantize(fmt.mul(fmt.quantize(a), fmt.quantize(b)))
        assert abs(float(prod) - (-3.375)) < 2.0 / fmt.scale

    def test_stochastic_rounding_unbiased(self):
        fmt = qz.QFormat(int_bits=3, frac_bits=4)
        x = jnp.full((20000,), 0.53)            # between grid points
        q = fmt.quantize(x, stochastic=True, key=jax.random.PRNGKey(0))
        mean = float(jnp.mean(fmt.dequantize(q)))
        assert abs(mean - 0.53) < 5e-3


class TestSymmetric:
    def test_scale_shape_per_feature(self):
        x = jax.random.normal(jax.random.PRNGKey(0), (64, 7))
        q = qz.quantize_symmetric(x, bits=8, axis=0)
        assert q.scale.shape == (1, 7)
        err = jnp.abs(q.dequantize() - x)
        assert float(jnp.max(err / q.scale)) <= 0.5 + 1e-5

    @given(bits=st.sampled_from([4, 8, 16]), seed=st.integers(0, 99))
    @settings(max_examples=15, deadline=None)
    def test_error_bounded_by_half_step(self, bits, seed):
        x = np.random.default_rng(seed).normal(size=(33,)).astype(
            np.float32)
        q = qz.quantize_symmetric(jnp.asarray(x), bits=bits)
        err = np.abs(np.asarray(q.dequantize()) - x)
        assert err.max() <= float(q.scale) * 0.5 + 1e-6


class TestHybridDot:
    def test_exact_vs_int64(self):
        rng = np.random.default_rng(0)
        a = rng.integers(-32768, 32767, (37, 130), dtype=np.int16)
        b = rng.integers(-32768, 32767, (130, 5), dtype=np.int16)
        want = (a.astype(np.int64) @ b.astype(np.int64)).astype(np.float64)
        got = np.asarray(qz.hybrid_dot(jnp.asarray(a), jnp.asarray(b)),
                         np.float64)
        rel = np.abs(got - want) / np.maximum(np.abs(want), 1.0)
        assert rel.max() < 1e-6          # f32 combine rounding only

    def test_int8_path(self):
        rng = np.random.default_rng(1)
        a = rng.integers(-128, 127, (16, 300), dtype=np.int8)
        b = rng.integers(-128, 127, (300, 8), dtype=np.int8)
        want = a.astype(np.int64) @ b.astype(np.int64)
        got = np.asarray(qz.hybrid_dot(jnp.asarray(a), jnp.asarray(b)))
        np.testing.assert_allclose(got, want, rtol=1e-6)

    def test_chunking_safe_for_large_k(self):
        # K large enough that a naive int32 dot of int16 operands would
        # overflow — the limb decomposition must stay exact
        rng = np.random.default_rng(2)
        a = rng.integers(-32768, 32767, (4, 9000), dtype=np.int16)
        b = rng.integers(-32768, 32767, (9000, 3), dtype=np.int16)
        want = (a.astype(np.int64) @ b.astype(np.int64)).astype(np.float64)
        got = np.asarray(qz.hybrid_dot(jnp.asarray(a), jnp.asarray(b)),
                         np.float64)
        rel = np.abs(got - want) / np.maximum(np.abs(want), 1.0)
        assert rel.max() < 1e-5


class TestInt8Limbs:
    @pytest.mark.parametrize("dtype,n_limbs", [(np.int8, 1), (np.uint8, 2),
                                               (np.int16, 3)])
    def test_signed_limbs_reconstruct_every_value(self, dtype, n_limbs):
        """Every value of the dtype splits into true int8 limbs whose
        weighted sum is exact (int16's 32640..32767 need the third)."""
        info = np.iinfo(dtype)
        x = np.arange(info.min, info.max + 1).astype(dtype)
        limbs = qz.int8_limbs(jnp.asarray(x))
        assert len(limbs) == n_limbs
        assert all(l.dtype == jnp.int8 for _, l in limbs)
        back = sum(int(w) * np.asarray(l, np.int64) for w, l in limbs)
        np.testing.assert_array_equal(back, x.astype(np.int64))


class TestErrorFeedback:
    def test_accumulated_error_stays_bounded(self):
        key = jax.random.PRNGKey(0)
        g = jax.random.normal(key, (256,))
        err = jnp.zeros_like(g)
        for i in range(20):
            q, err = qz.ef_quantize(g, err, bits=4)
        # EF residual must not blow up (stays within one quant step)
        assert float(jnp.max(jnp.abs(err))) <= float(q.scale) * 0.51

    def test_ef_mean_recovered(self):
        # with error feedback, the time-average of dequantized grads
        # converges to the true gradient
        g = jnp.full((64,), 0.0173)
        err = jnp.zeros_like(g)
        acc = jnp.zeros_like(g)
        n = 50
        for _ in range(n):
            q, err = qz.ef_quantize(g, err, bits=4)
            acc = acc + q.dequantize()
        np.testing.assert_allclose(np.asarray(acc / n), 0.0173, atol=2e-4)
