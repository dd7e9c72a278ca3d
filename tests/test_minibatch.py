"""The on-device minibatch sampler (``core.minibatch``): epoch-exact
coverage, numpy-oracle gradient parity, scan==python parity, counter
exactness under cadence, and the guard rails."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import datasets, minibatch as mb, make_cpu_grid
from repro.core.mlalgos import LinReg, train_linreg, train_kmeans
from repro.distributed.merge_plan import MergePlan, SlowMo

KEY = jax.random.PRNGKey(0)


def _coverage_counts(per, b, seed, epoch):
    """How often each resident slot index is selected (with a valid
    mask) during one epoch window."""
    E = mb.epoch_steps(per, b)
    counts = np.zeros((per,), np.int64)
    for pos in range(E):
        idx, mask = jax.device_get(
            mb.batch_indices(per, b, seed, epoch * E + pos))
        counts[np.asarray(idx)[np.asarray(mask) > 0]] += 1
    return counts


class TestSchedule:
    @pytest.mark.parametrize("per,b", [(32, 8), (33, 8), (128, 32),
                                       (7, 3), (16, 16), (5, 1)])
    def test_epoch_exact_coverage(self, per, b):
        """Every resident slot is visited exactly once per epoch
        window, whatever the divisibility."""
        for epoch in (0, 1, 3):
            counts = _coverage_counts(per, b, seed=0, epoch=epoch)
            np.testing.assert_array_equal(counts, np.ones((per,)))

    def test_epochs_reshuffle(self):
        """Different epochs draw different permutations (fold_in on the
        epoch index), same epoch is deterministic."""
        per, b = 64, 16
        first = [np.asarray(jax.device_get(
            mb.batch_indices(per, b, 0, t)[0])) for t in range(4)]
        again = [np.asarray(jax.device_get(
            mb.batch_indices(per, b, 0, t)[0])) for t in range(4)]
        second_epoch = [np.asarray(jax.device_get(
            mb.batch_indices(per, b, 0, 4 + t)[0])) for t in range(4)]
        for a, c in zip(first, again):
            np.testing.assert_array_equal(a, c)
        assert not all(np.array_equal(a, s)
                       for a, s in zip(first, second_epoch))

    def test_seed_changes_schedule(self):
        per, b = 64, 16
        i0 = np.asarray(jax.device_get(mb.batch_indices(per, b, 0, 0)[0]))
        i1 = np.asarray(jax.device_get(mb.batch_indices(per, b, 1, 0)[0]))
        assert not np.array_equal(i0, i1)

    def test_pad_slots_masked_not_counted(self):
        """per % b != 0: the last batch of an epoch carries pad slots
        with a zero mask — exactly E*b - per of them."""
        per, b = 33, 8
        E = mb.epoch_steps(per, b)
        total_valid = 0
        for pos in range(E):
            _, mask = jax.device_get(mb.batch_indices(per, b, 0, pos))
            total_valid += int(np.sum(np.asarray(mask)))
        assert total_valid == per
        assert E * b - per == 7

    def test_batch_size_validation(self):
        lf = uf = lambda *a: None
        with pytest.raises(ValueError, match="batch_size"):
            mb.minibatch_fns(lf, uf, jnp.zeros(()), rows_per_vdpu=8,
                             batch_size=0)
        with pytest.raises(ValueError, match="batch_size"):
            mb.minibatch_fns(lf, uf, jnp.zeros(()), rows_per_vdpu=8,
                             batch_size=9)


# optional hypothesis sweep over the same invariant (the container may
# not ship hypothesis; the parametrized cases above always run)
try:
    import hypothesis  # noqa: F401
    from hypothesis import given, settings, strategies as st

    @given(per=st.integers(2, 96), frac=st.integers(1, 96),
           seed=st.integers(0, 100), epoch=st.integers(0, 5))
    @settings(max_examples=40, deadline=None)
    def test_epoch_coverage_property(per, frac, seed, epoch):
        """Hypothesis: for ANY (rows_per_vdpu, batch_size, seed, epoch)
        every resident slot is visited exactly once per epoch window."""
        b = 1 + frac % per
        counts = _coverage_counts(per, b, seed, epoch)
        np.testing.assert_array_equal(counts, np.ones((per,)))
except ImportError:
    pass


class TestMinibatchTraining:
    def _problem(self, V=4, per=32, d=6):
        X = np.asarray(jax.random.normal(KEY, (V * per, d)), np.float32)
        w_true = np.linspace(-1.0, 1.0, d).astype(np.float32)
        y = (X @ w_true).astype(np.float32)
        return V, per, d, X, y

    def test_numpy_oracle_parity_cadence1(self):
        """The engine's sampled-gradient step against a numpy replica
        driving the SAME schedule (batch_indices is the one definition,
        called eagerly here): per-vDPU gather, schedule mask, per/valid
        scaling, global normalisation."""
        V, per, d, X, y = self._problem()
        b, lr, steps, seed = 8, 0.05, 40, 3
        grid = make_cpu_grid(V)
        res = train_linreg(grid, jnp.asarray(X), jnp.asarray(y), lr=lr,
                           steps=steps, batch_size=b, sample_seed=seed)
        n = V * per
        w = np.zeros((d,), np.float32)
        for t in range(steps):
            idx, mask = jax.device_get(mb.batch_indices(per, b, seed, t))
            idx, mask = np.asarray(idx), np.asarray(mask)
            scale = per / max(mask.sum(), 1.0)
            g = np.zeros((d,), np.float32)
            for v in range(V):
                Xv = X[v * per:(v + 1) * per][idx]
                yv = y[v * per:(v + 1) * per][idx]
                r = (Xv @ w - yv) * mask
                g += scale * (Xv.T @ r).astype(np.float32)
            w = w - lr * g / n
        np.testing.assert_allclose(np.asarray(res.w), w, rtol=1e-4,
                                   atol=1e-5)

    def test_scan_matches_python_engine(self):
        V, per, d, X, y = self._problem()
        grid = make_cpu_grid(V)
        kw = dict(lr=0.05, steps=24, batch_size=8)
        r_scan = train_linreg(grid, jnp.asarray(X), jnp.asarray(y), **kw)
        r_py = train_linreg(grid, jnp.asarray(X), jnp.asarray(y),
                            engine="python", **kw)
        np.testing.assert_array_equal(np.asarray(r_scan.w),
                                      np.asarray(r_py.w))

    def test_scan_matches_python_engine_cadence4(self):
        V, per, d, X, y = self._problem()
        grid = make_cpu_grid(V)
        kw = dict(lr=0.05, steps=24, batch_size=8, merge_every=4)
        r_scan = train_linreg(grid, jnp.asarray(X), jnp.asarray(y), **kw)
        r_py = train_linreg(grid, jnp.asarray(X), jnp.asarray(y),
                            engine="python", **kw)
        np.testing.assert_array_equal(np.asarray(r_scan.w),
                                      np.asarray(r_py.w))

    def test_counter_stays_exact_under_cadence(self):
        """The sampler's float32 step counter must land on exact
        integers through cadence averaging and remainder rounds (the
        epoch schedule depends on it)."""
        V, per, d, X, y = self._problem()
        grid = make_cpu_grid(V)
        program = LinReg(lr=0.05).bind(grid, jnp.asarray(X),
                                       jnp.asarray(y))
        lf, uf, s0, unwrap = program._triple(8, 0)
        for steps, k in [(24, 4), (25, 4), (10, 1)]:
            state, _ = grid.fit(init_state=s0, local_fn=lf,
                                update_fn=uf, data=program.data,
                                steps=steps, merge_every=k,
                                consts=program.array_consts)
            assert float(state[1]) == float(steps)

    def test_full_batch_unchanged_and_minibatch_converges(self):
        """batch_size=None is the untouched engine; a sampled run still
        reaches the neighbourhood of the solution (epoch-exact SGD)."""
        V, per, d, X, y = self._problem()
        grid = make_cpu_grid(V)
        full = train_linreg(grid, jnp.asarray(X), jnp.asarray(y),
                            lr=0.05, steps=160)
        mini = train_linreg(grid, jnp.asarray(X), jnp.asarray(y),
                            lr=0.05, steps=160, batch_size=8)
        w_true = np.linspace(-1.0, 1.0, d).astype(np.float32)
        assert np.linalg.norm(np.asarray(full.w) - w_true) < 0.05
        assert np.linalg.norm(np.asarray(mini.w) - w_true) < 0.2

    def test_determinism_and_seed_sensitivity(self):
        V, per, d, X, y = self._problem()
        grid = make_cpu_grid(V)
        kw = dict(lr=0.05, steps=20, batch_size=8)
        r1 = train_linreg(grid, jnp.asarray(X), jnp.asarray(y),
                          sample_seed=0, **kw)
        r2 = train_linreg(grid, jnp.asarray(X), jnp.asarray(y),
                          sample_seed=0, **kw)
        r3 = train_linreg(grid, jnp.asarray(X), jnp.asarray(y),
                          sample_seed=7, **kw)
        np.testing.assert_array_equal(np.asarray(r1.w), np.asarray(r2.w))
        assert not np.array_equal(np.asarray(r1.w), np.asarray(r3.w))

    def test_stateful_outer_refused(self):
        """SlowMo/Nesterov would integrate the step counter into their
        momentum — refused with a clear error, not silently wrong."""
        V, per, d, X, y = self._problem()
        grid = make_cpu_grid(V)
        with pytest.raises(ValueError, match="outer optimizer"):
            train_linreg(grid, jnp.asarray(X), jnp.asarray(y), lr=0.05,
                         steps=8, batch_size=8,
                         merge_plan=MergePlan(cadence=4,
                                              outer=SlowMo()))

    def test_minibatch_kmeans_recovers_blobs(self):
        X, _, centers = datasets.blobs(KEY, 2000, 5, k=4, spread=0.15)
        grid = make_cpu_grid(8)
        res = train_kmeans(grid, X, 4, iters=20, batch_size=32)
        dist = jnp.linalg.norm(res.centroids[:, None] - centers[None],
                               axis=-1)
        assert float(jnp.max(jnp.min(dist, axis=0))) < 0.5

    def test_history_lengths_match_steps(self):
        V, per, d, X, y = self._problem()
        grid = make_cpu_grid(V)
        res = train_linreg(grid, jnp.asarray(X), jnp.asarray(y),
                           lr=0.05, steps=13, batch_size=8,
                           merge_every=4)
        assert len(res.history) == 13
