"""The MergePlan subsystem (``distributed.merge_plan``): plan spellings,
SlowMo outer momentum, top-k sparsified merges, adaptive cadence.

Contracts pinned here:
  * ``fit(merge_plan=None)``, ``fit(merge_plan=MergePlan())`` and the
    legacy kwarg spellings are bit-exact with each other and with the
    python-engine oracle for all four mlalgos (the PR 3 engine is the
    default plan's code path, untouched),
  * SlowMo matches a hand-rolled numpy oracle over 200 steps at
    cadence 1 and 4, with and without the int8+EF wire, and its
    momentum buffer continues across ``fit`` calls and Trainer
    checkpoints,
  * top-k sparsified merges round-trip through the EF buffer (kept +
    residual == target), match a numpy oracle, and cost fewer analytic
    wire bytes than the dense int8 row,
  * the adaptive-cadence controller only ever grows ``k`` and re-uses
    compiled runners across repeated cadences,
  * dtree's cadence fallback warns (structured, once per fit) instead
    of being doc-only.
"""

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import datasets, make_cpu_grid
from repro.core.mlalgos import (make_linreg_step, train_linreg,
                                train_logreg, train_kmeans, train_dtree)
from repro.core.mlalgos.linreg import closed_form
from repro.distributed import compression as comp
from repro.distributed.compression import CompressionConfig
from repro.distributed.merge_plan import (MergePlan, OuterOptimizer,
                                          AverageCommit, SlowMo,
                                          AdaptiveCadence,
                                          MergeFallbackWarning)
from repro.runtime import Trainer, TrainerConfig

KEY = jax.random.PRNGKey(0)
INT8 = CompressionConfig(bits=8)


class TestPlanSpellings:
    """merge_plan= and the legacy kwargs are two spellings of one
    thing; the default plan is the PR 3 engine bit-exactly."""

    def test_default_plan_bit_exact_linreg(self):
        X, y, _ = datasets.regression(KEY, 400, 8)
        grid = make_cpu_grid(8)
        r_none = train_linreg(grid, X, y, lr=0.05, steps=40)
        r_plan = train_linreg(grid, X, y, lr=0.05, steps=40,
                              merge_plan=MergePlan())
        r_py = train_linreg(grid, X, y, lr=0.05, steps=40,
                            engine="python")
        np.testing.assert_array_equal(np.asarray(r_none.w),
                                      np.asarray(r_plan.w))
        np.testing.assert_array_equal(np.asarray(r_plan.w),
                                      np.asarray(r_py.w))

    def test_default_plan_bit_exact_logreg(self):
        X, y, _ = datasets.binary_classification(KEY, 400, 6)
        grid = make_cpu_grid(8)
        r_plan = train_logreg(grid, X, y, lr=0.5, steps=30,
                              merge_plan=MergePlan())
        r_py = train_logreg(grid, X, y, lr=0.5, steps=30,
                            engine="python")
        np.testing.assert_array_equal(np.asarray(r_plan.w),
                                      np.asarray(r_py.w))

    def test_default_plan_bit_exact_kmeans(self):
        X, _, _ = datasets.blobs(KEY, 500, 4, k=3, spread=0.3)
        grid = make_cpu_grid(8)
        r_plan = train_kmeans(grid, X, 3, iters=8,
                              merge_plan=MergePlan())
        r_py = train_kmeans(grid, X, 3, iters=8, engine="python")
        np.testing.assert_array_equal(np.asarray(r_plan.centroids),
                                      np.asarray(r_py.centroids))

    def test_default_plan_dtree_inert_and_silent(self):
        X, y = datasets.mixture_classification(KEY, 600, 6, 2)
        grid = make_cpu_grid(8)
        with warnings.catch_warnings():
            warnings.simplefilter("error", MergeFallbackWarning)
            r0 = train_dtree(grid, X, y, max_depth=3)
            r1 = train_dtree(grid, X, y, max_depth=3,
                             merge_plan=MergePlan())
        np.testing.assert_array_equal(np.asarray(r0.tree.feature),
                                      np.asarray(r1.tree.feature))

    def test_legacy_kwargs_equal_plan_spelling(self):
        X, y, _ = datasets.regression(KEY, 320, 6)
        grid = make_cpu_grid(8)
        cases = [
            (dict(merge_every=4), MergePlan(cadence=4)),
            (dict(overlap_merge=True), MergePlan(overlap=True)),
            (dict(merge_compression=INT8),
             MergePlan(compression=INT8)),
            (dict(merge_every=4, overlap_merge=True,
                  merge_compression=INT8),
             MergePlan(cadence=4, overlap=True, compression=INT8)),
        ]
        for kwargs, plan in cases:
            r_legacy = train_linreg(grid, X, y, lr=0.05, steps=16,
                                    **kwargs)
            r_plan = train_linreg(grid, X, y, lr=0.05, steps=16,
                                  merge_plan=plan)
            np.testing.assert_array_equal(
                np.asarray(r_legacy.w), np.asarray(r_plan.w)), kwargs

    def test_mixed_spellings_rejected(self):
        X, y, _ = datasets.regression(KEY, 100, 4)
        grid = make_cpu_grid(4)
        data, n, lf, uf, w0 = make_linreg_step(grid, X, y, lr=0.05)
        with pytest.raises(ValueError, match="not both"):
            grid.fit(init_state=w0, local_fn=lf, update_fn=uf,
                     data=data, steps=4, merge_every=2,
                     merge_plan=MergePlan(cadence=2))

    def test_plan_validation(self):
        with pytest.raises(ValueError, match="cadence"):
            MergePlan(cadence=0)
        with pytest.raises(ValueError, match="OuterOptimizer"):
            MergePlan(outer="slowmo")
        with pytest.raises(ValueError, match="overlap"):
            MergePlan(overlap=True, outer=AdaptiveCadence())

    def test_compression_config_validation(self):
        with pytest.raises(ValueError, match="top_k_frac"):
            CompressionConfig(bits=None)
        with pytest.raises(ValueError, match="top_k_frac"):
            CompressionConfig(top_k_frac=1.5)
        with pytest.raises(ValueError, match="bits"):
            CompressionConfig(bits=1, top_k_frac=0.5)
        CompressionConfig(bits=None, top_k_frac=0.5)   # legal

    def test_config_merge_plan_builder(self):
        from repro.configs.pim_ml import PimMLConfig
        plan = PimMLConfig(merge_outer="slowmo", merge_every=4,
                           merge_top_k_frac=0.25).merge_plan()
        assert plan.cadence == 4
        assert isinstance(plan.outer, SlowMo)
        assert plan.compression.top_k_frac == 0.25
        assert plan.compression.bits is None
        assert PimMLConfig().merge_plan().compression is None
        with pytest.raises(ValueError, match="merge_outer"):
            PimMLConfig(merge_outer="slow_mo").merge_plan()


def _ef_quantize_np(target, bits=8):
    qmax = 2 ** (bits - 1) - 1
    amax = np.max(np.abs(target))
    scale = max(amax, 1e-12) / qmax
    q = np.clip(np.round(target / scale), -qmax - 1, qmax)
    deq = (q * scale).astype(np.float32)
    return deq, target - deq


class TestSlowMoOracle:
    """The engine's SlowMo commit against a hand-rolled numpy replica
    over 200 steps — cadence 1 and 4, exact and int8+EF wires."""

    BETA, ALPHA = 0.5, 1.0

    def _setup(self):
        V, per, d, lr = 4, 32, 6, 0.05
        X = np.asarray(jax.random.normal(KEY, (V * per, d)), np.float32)
        w_true = np.linspace(-1.0, 1.0, d).astype(np.float32)
        y = X @ w_true
        return V, per, d, lr, X, y

    def _commit(self, w, proposed, m):
        """SlowMo: m' = beta*m - delta, w' = w - alpha*m'."""
        delta = proposed - w
        m = self.BETA * m - delta
        return (w - self.ALPHA * m).astype(np.float32), m

    def _oracle_cadence1(self, V, per, d, lr, X, y, steps, compressed):
        n = V * per
        w = np.zeros((d,), np.float32)
        m = np.zeros((d,), np.float32)
        e_g = np.zeros((d,), np.float32)
        e_l = np.zeros((), np.float32)
        for _ in range(steps):
            g = np.zeros((d,), np.float32)
            for v in range(V):
                Xv, yv = X[v * per:(v + 1) * per], y[v * per:(v + 1) * per]
                g += (Xv.T @ (Xv @ w - yv)).astype(np.float32)
            if compressed:
                g, e_g = _ef_quantize_np(g + e_g)
                # the loss leaf quantizes too (same wire) — it does not
                # touch w, but keep the replica faithful
                e_l = e_l
            proposed = w - lr * g / n
            w, m = self._commit(w, proposed, m)
        return w

    def _oracle_cadence_k(self, V, per, d, lr, X, y, steps, k,
                          compressed):
        n = V * per
        w = np.zeros((d,), np.float32)
        m = np.zeros((d,), np.float32)
        e = np.zeros((d,), np.float32)
        done = 0
        while done < steps:
            kk = min(k, steps - done)
            lanes = []
            for v in range(V):
                Xv, yv = X[v * per:(v + 1) * per], y[v * per:(v + 1) * per]
                wv = w.copy()
                for _ in range(kk):
                    g = V * (Xv.T @ (Xv @ wv - yv)).astype(np.float32)
                    wv = wv - lr * g / n
                lanes.append(wv)
            avg = np.mean(lanes, axis=0).astype(np.float32)
            if compressed:
                avg, e = _ef_quantize_np(avg + e)
            w, m = self._commit(w, avg, m)
            done += kk
        return w

    def test_cadence1_exact_matches_oracle(self):
        V, per, d, lr, X, y = self._setup()
        grid = make_cpu_grid(V)
        res = train_linreg(grid, jnp.asarray(X), jnp.asarray(y), lr=lr,
                           steps=200, merge_plan=MergePlan(
                               outer=SlowMo(beta=self.BETA,
                                            outer_lr=self.ALPHA)))
        w_oracle = self._oracle_cadence1(V, per, d, lr, X, y, 200,
                                         False)
        np.testing.assert_allclose(np.asarray(res.w), w_oracle,
                                   rtol=1e-3, atol=1e-3)

    def test_cadence1_int8_ef_matches_oracle(self):
        V, per, d, lr, X, y = self._setup()
        grid = make_cpu_grid(V)
        res = train_linreg(grid, jnp.asarray(X), jnp.asarray(y), lr=lr,
                           steps=200, merge_plan=MergePlan(
                               compression=INT8,
                               outer=SlowMo(beta=self.BETA,
                                            outer_lr=self.ALPHA)))
        w_oracle = self._oracle_cadence1(V, per, d, lr, X, y, 200, True)
        np.testing.assert_allclose(np.asarray(res.w), w_oracle,
                                   rtol=2e-3, atol=2e-3)

    def test_cadence4_exact_matches_oracle(self):
        V, per, d, lr, X, y = self._setup()
        grid = make_cpu_grid(V)
        res = train_linreg(grid, jnp.asarray(X), jnp.asarray(y), lr=lr,
                           steps=200, merge_plan=MergePlan(
                               cadence=4,
                               outer=SlowMo(beta=self.BETA,
                                            outer_lr=self.ALPHA)))
        w_oracle = self._oracle_cadence_k(V, per, d, lr, X, y, 200, 4,
                                          False)
        np.testing.assert_allclose(np.asarray(res.w), w_oracle,
                                   rtol=1e-3, atol=1e-3)

    def test_cadence4_int8_ef_matches_oracle(self):
        V, per, d, lr, X, y = self._setup()
        grid = make_cpu_grid(V)
        res = train_linreg(grid, jnp.asarray(X), jnp.asarray(y), lr=lr,
                           steps=200, merge_plan=MergePlan(
                               cadence=4, compression=INT8,
                               outer=SlowMo(beta=self.BETA,
                                            outer_lr=self.ALPHA)))
        w_oracle = self._oracle_cadence_k(V, per, d, lr, X, y, 200, 4,
                                          True)
        np.testing.assert_allclose(np.asarray(res.w), w_oracle,
                                   rtol=2e-3, atol=2e-3)

    def test_slowmo_converges_no_worse_than_average(self):
        """The point of the outer momentum: at cadence 4 SlowMo reaches
        the closed-form solution at least as fast as the plain
        average."""
        V, per, d, lr, X, y = self._setup()
        w_star = np.asarray(closed_form(jnp.asarray(X), jnp.asarray(y)))
        grid = make_cpu_grid(V)
        err = {}
        for name, plan in [("avg", MergePlan(cadence=4)),
                           ("slowmo", MergePlan(cadence=4,
                                                outer=SlowMo(beta=0.5)))]:
            res = train_linreg(grid, jnp.asarray(X), jnp.asarray(y),
                               lr=lr, steps=120, merge_plan=plan)
            err[name] = float(np.linalg.norm(np.asarray(res.w) - w_star))
        assert err["slowmo"] <= err["avg"] * 1.05 + 1e-5, err

    def test_beta0_alpha1_recovers_average(self):
        V, per, d, lr, X, y = self._setup()
        grid = make_cpu_grid(V)
        r_avg = train_linreg(grid, jnp.asarray(X), jnp.asarray(y),
                             lr=lr, steps=40, merge_every=4)
        r_sm = train_linreg(grid, jnp.asarray(X), jnp.asarray(y),
                            lr=lr, steps=40, merge_plan=MergePlan(
                                cadence=4, outer=SlowMo(beta=0.0,
                                                        outer_lr=1.0)))
        np.testing.assert_allclose(np.asarray(r_sm.w),
                                   np.asarray(r_avg.w),
                                   rtol=1e-5, atol=1e-6)

    def test_scan_matches_python_engine(self):
        X, y, _ = datasets.regression(KEY, 320, 6)
        grid = make_cpu_grid(4)
        plan = MergePlan(cadence=4, outer=SlowMo(beta=0.5))
        r_scan = train_linreg(grid, X, y, lr=0.05, steps=24,
                              merge_plan=plan)
        r_py = train_linreg(grid, X, y, lr=0.05, steps=24,
                            merge_plan=plan, engine="python")
        np.testing.assert_array_equal(np.asarray(r_scan.w),
                                      np.asarray(r_py.w))
        assert len(r_scan.history) == len(r_py.history) == 24


class TestSlowMoContinuation:
    def test_momentum_continues_across_fits(self):
        X, y, _ = datasets.regression(KEY, 320, 6)
        grid = make_cpu_grid(4)
        data, n, lf, uf, w0 = make_linreg_step(grid, X, y, lr=0.05)
        plan = MergePlan(cadence=4, outer=SlowMo(beta=0.5))

        w_one, _ = grid.fit(init_state=w0, local_fn=lf, update_fn=uf,
                            data=data, steps=96, merge_plan=plan)
        holder: dict = {}
        w_half, _ = grid.fit(init_state=w0, local_fn=lf, update_fn=uf,
                             data=data, steps=48, merge_plan=plan,
                             merge_state=holder)
        assert "momentum" in holder
        w_two, _ = grid.fit(init_state=w_half, local_fn=lf,
                            update_fn=uf, data=data, steps=48,
                            merge_plan=plan, merge_state=holder)
        np.testing.assert_allclose(np.asarray(w_two), np.asarray(w_one),
                                   rtol=1e-6, atol=1e-7)

    def test_dropping_momentum_between_fits_diverges(self):
        X, y, _ = datasets.regression(KEY, 320, 6)
        grid = make_cpu_grid(4)
        data, n, lf, uf, w0 = make_linreg_step(grid, X, y, lr=0.05)
        plan = MergePlan(cadence=4, outer=SlowMo(beta=0.5))
        holder: dict = {}
        w_half, _ = grid.fit(init_state=w0, local_fn=lf, update_fn=uf,
                             data=data, steps=48, merge_plan=plan,
                             merge_state=holder)
        w_cont, _ = grid.fit(init_state=w_half, local_fn=lf,
                             update_fn=uf, data=data, steps=48,
                             merge_plan=plan, merge_state=holder)
        w_drop, _ = grid.fit(init_state=w_half, local_fn=lf,
                             update_fn=uf, data=data, steps=48,
                             merge_plan=plan)
        assert not np.array_equal(np.asarray(w_cont), np.asarray(w_drop))

    def test_trainer_checkpoints_momentum(self, tmp_path):
        """The v2 checkpoint layout carries the outer-momentum leaf
        next to the EF buffer and restores it into the holder."""
        from repro.optim.optimizers import slow_momentum

        def step_fn(state, batch):
            w = state["w"] - 0.1 * batch["g"]
            return {"w": w}, {"loss": jnp.sum(w ** 2)}

        opt = slow_momentum(1.0, beta=0.5)
        mom0 = opt.init({"w": jnp.asarray([0.25, -0.5, 1.0])})
        holder = {"error": {"g": jnp.asarray([0.5, -0.25, 0.0])},
                  "momentum": mom0}
        cfg = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=5,
                            log_every=100, merge_compression=INT8)
        tr = Trainer(step_fn, {"w": jnp.ones((3,))},
                     lambda s: {"g": jnp.ones((3,))}, cfg,
                     merge_state=holder)
        tr.run(10)

        holder2 = {"error": {"g": jnp.zeros((3,))},
                   "momentum": opt.init({"w": jnp.zeros((3,))})}
        tr2 = Trainer(step_fn, {"w": jnp.ones((3,))},
                      lambda s: {"g": jnp.ones((3,))}, cfg,
                      merge_state=holder2)
        assert tr2.start_step == 10
        np.testing.assert_allclose(np.asarray(holder2["error"]["g"]),
                                   np.asarray(holder["error"]["g"]))
        np.testing.assert_allclose(
            np.asarray(holder2["momentum"].inner["w"]),
            np.asarray(holder["momentum"].inner["w"]))

    def test_trainer_merge_plan_config_spelling(self, tmp_path):
        """TrainerConfig.merge_plan drives cadence/compression; mixing
        it with the legacy knobs is rejected."""
        plan = MergePlan(cadence=2, compression=INT8)

        def step_fn(state, batch):
            return {"w": state["w"] - 0.1}, {"loss": jnp.zeros(())}

        cfg = TrainerConfig(ckpt_dir=str(tmp_path), merge_plan=plan)
        tr = Trainer(step_fn, {"w": jnp.ones((2,))}, lambda s: {}, cfg,
                     merge_state={"error": {"g": jnp.zeros((2,))}})
        assert tr._merge_every == 2
        assert tr._compression_tag() == repr(INT8)
        with pytest.raises(ValueError, match="not both"):
            Trainer(step_fn, {"w": jnp.ones((2,))}, lambda s: {},
                    TrainerConfig(merge_plan=plan, merge_every=4))
        # adaptive plans are rejected: the Trainer's boundary math
        # assumes a fixed cadence, the controller re-decides k mid-run
        with pytest.raises(ValueError, match="adaptive"):
            Trainer(step_fn, {"w": jnp.ones((2,))}, lambda s: {},
                    TrainerConfig(merge_plan=MergePlan(
                        outer=AdaptiveCadence())))


def _topk_np(target, frac):
    flat = np.abs(target).reshape(-1)
    k = max(1, int(flat.size * frac))
    thresh = np.sort(flat)[::-1][k - 1]
    mask = (np.abs(target) >= thresh).astype(target.dtype)
    return target * mask


class TestTopK:
    def test_exactly_k_survive_under_ties(self):
        """Selection is by index, not threshold: a tied (here all-zero)
        target must still keep exactly k entries, or wire_bytes'
        k-entry model silently under-counts the traffic."""
        from repro.core import quantize as qz
        kept = qz.topk_keep(jnp.zeros((32,), jnp.float32), 0.25)
        # mask has exactly 8 surviving slots — with a zero target the
        # kept values are zero, but a tied nonzero target proves it:
        kept2 = qz.topk_keep(jnp.ones((32,), jnp.float32), 0.25)
        assert int((np.asarray(kept2) != 0).sum()) == 8
        np.testing.assert_array_equal(np.asarray(kept), 0.0)

    def test_ef_round_trip_raw_values(self):
        """bits=None: kept values cross exact, so kept + residual must
        reconstruct the error-fed target exactly."""
        cfg = CompressionConfig(bits=None, top_k_frac=0.25)
        x = jnp.asarray(np.linspace(-3.0, 5.0, 32), jnp.float32)
        e = jnp.asarray(np.linspace(0.1, -0.1, 32), jnp.float32)
        out, new_e = comp.ef_compress_tree({"g": x}, {"g": e}, cfg)
        kept = np.asarray(out["g"])
        assert int((kept != 0).sum()) == 8          # 25% of 32
        np.testing.assert_allclose(kept + np.asarray(new_e["g"]),
                                   np.asarray(x + e), atol=1e-6)
        np.testing.assert_array_equal(
            kept, _topk_np(np.asarray(x + e), 0.25))

    def test_ef_round_trip_int8_values(self):
        """bits=8: the quantization residual folds into the same EF
        buffer — kept + residual still reconstructs the target."""
        cfg = CompressionConfig(bits=8, top_k_frac=0.25)
        x = jnp.asarray(np.linspace(-3.0, 5.0, 32), jnp.float32)
        e = jnp.zeros((32,), jnp.float32)
        out, new_e = comp.ef_compress_tree({"g": x}, {"g": e}, cfg)
        np.testing.assert_allclose(
            np.asarray(out["g"] + new_e["g"]), np.asarray(x), atol=1e-6)

    def test_integer_leaves_pass_through(self):
        cfg = CompressionConfig(bits=8, top_k_frac=0.25)
        tree = {"counts": jnp.asarray([5, 0, 9], jnp.int32),
                "sums": jnp.linspace(-1.0, 1.0, 16)}
        err = comp.init_error_state(tree)
        out, _ = comp.ef_compress_tree(tree, err, cfg)
        np.testing.assert_array_equal(np.asarray(out["counts"]),
                                      [5, 0, 9])
        assert out["counts"].dtype == jnp.int32

    def test_engine_matches_numpy_oracle(self):
        """Cadence-1 top-k+int8 EF merges over 200 steps against a
        numpy replica of the sparsified wire."""
        V, per, d, lr, frac = 4, 32, 6, 0.05, 0.5
        X = np.asarray(jax.random.normal(KEY, (V * per, d)), np.float32)
        y = X @ np.linspace(-1.0, 1.0, d).astype(np.float32)
        n = V * per
        w = np.zeros((d,), np.float32)
        e = np.zeros((d,), np.float32)
        for _ in range(200):
            g = np.zeros((d,), np.float32)
            for v in range(V):
                Xv, yv = X[v * per:(v + 1) * per], y[v * per:(v + 1) * per]
                g += (Xv.T @ (Xv @ w - yv)).astype(np.float32)
            target = g + e
            kept = _topk_np(target, frac)
            deq, _ = _ef_quantize_np(kept)
            e = target - deq
            w = w - lr * deq / n
        grid = make_cpu_grid(V)
        res = train_linreg(
            grid, jnp.asarray(X), jnp.asarray(y), lr=lr, steps=200,
            merge_compression=CompressionConfig(bits=8,
                                                top_k_frac=frac))
        np.testing.assert_allclose(np.asarray(res.w), w,
                                   rtol=2e-3, atol=2e-3)

    def test_topk_converges_within_tolerance_of_exact(self):
        X, y, _ = datasets.regression(KEY, 800, 8)
        w_star = np.asarray(closed_form(X, y))
        grid = make_cpu_grid(8)
        r_exact = train_linreg(grid, X, y, lr=0.05, steps=200)
        r_topk = train_linreg(
            grid, X, y, lr=0.05, steps=200,
            merge_compression=CompressionConfig(bits=8,
                                                top_k_frac=0.25))
        err_exact = float(np.linalg.norm(np.asarray(r_exact.w) - w_star))
        err_topk = float(np.linalg.norm(np.asarray(r_topk.w) - w_star))
        assert err_topk <= 1.5 * err_exact + 0.05, (err_exact, err_topk)

    def test_state_wire_rides_the_delta(self):
        """At cadence k the top-k wire must sparsify the merge *delta*,
        not the state (top-k of a state zeroes most of the model every
        round).  Convergence within tolerance of the int8 row is the
        observable."""
        X, y, _ = datasets.regression(KEY, 800, 8)
        w_star = np.asarray(closed_form(X, y))
        grid = make_cpu_grid(8)
        errs = {}
        for name, cfg in [("int8", INT8),
                          ("topk", CompressionConfig(bits=8,
                                                     top_k_frac=0.25))]:
            res = train_linreg(grid, X, y, lr=0.05, steps=200,
                               merge_every=4, merge_compression=cfg)
            errs[name] = float(np.linalg.norm(np.asarray(res.w)
                                              - w_star))
        assert errs["topk"] <= 2.0 * errs["int8"] + 0.05, errs

    def test_wire_bytes_accounting(self):
        tree = {"g": jnp.zeros((100,), jnp.float32),
                "hist": jnp.zeros((10,), jnp.int32)}
        topk8 = CompressionConfig(bits=8, top_k_frac=0.1)
        # 10 kept values at 1 B + 10 exact 4 B indices + 4 B scale; ints
        # native
        assert comp.wire_bytes(tree, topk8) == 10 * (1 + 4) + 4 + 40
        topk_raw = CompressionConfig(bits=None, top_k_frac=0.1)
        # raw fp32 values, no scale
        assert comp.wire_bytes(tree, topk_raw) == 10 * (4 + 4) + 40
        # the acceptance inequality: top-k below the dense int8 row
        assert comp.wire_bytes(tree, topk8) < comp.wire_bytes(tree, INT8)

    def test_sparse_psum_ef_on_mesh(self):
        """The mesh-path collective: each participant sparsifies its
        error-fed slice; kept mass sums, dropped mass lands in the
        residual."""
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_host_mesh
        from repro.distributed import collectives as coll
        mesh = make_host_mesh(1, 1)
        x = jnp.asarray(np.linspace(-2.0, 6.0, 64), jnp.float32)
        e = jnp.zeros((64,), jnp.float32)

        def body(x, e):
            return coll.sparse_psum_ef(x, e, "data", frac=0.25,
                                       bits=None)

        out, new_e = shard_map(
            body, mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False)(x, e)
        assert int((np.asarray(out) != 0).sum()) == 16
        np.testing.assert_allclose(np.asarray(out + new_e),
                                   np.asarray(x), atol=1e-6)


class TestAdaptiveCadence:
    def _problem(self, v=8):
        X, y, _ = datasets.regression(KEY, 640, 8)
        grid = make_cpu_grid(v)
        return grid, X, y

    def test_cadence_trace_monotonic_and_grows(self):
        grid, X, y = self._problem()
        holder: dict = {}
        res = train_linreg(grid, X, y, lr=0.05, steps=120,
                           merge_plan=MergePlan(
                               outer=AdaptiveCadence(k_max=8)),
                           merge_state=holder)
        trace = holder["cadence_trace"]
        assert trace == sorted(trace)            # k never shrinks
        assert trace[-1] > trace[0]              # and actually grew
        assert trace[-1] <= 8
        assert len(res.history) == 120

    def test_compile_cache_reused_across_k_changes(self):
        """Each distinct k compiles once; a second adaptive fit over the
        same problem re-visits the same cadences and must add no new
        runner entries."""
        grid, X, y = self._problem()
        plan = MergePlan(outer=AdaptiveCadence(k_max=8))
        train_linreg(grid, X, y, lr=0.05, steps=120, merge_plan=plan)
        n_entries = len(grid._fit_cache)
        train_linreg(grid, X, y, lr=0.05, steps=120, merge_plan=plan)
        assert len(grid._fit_cache) == n_entries

    def test_converges(self):
        grid, X, y = self._problem()
        w_star = np.asarray(closed_form(X, y))
        res = train_linreg(grid, X, y, lr=0.05, steps=200,
                           merge_plan=MergePlan(
                               outer=AdaptiveCadence(k_max=16)))
        err = float(np.linalg.norm(np.asarray(res.w) - w_star))
        base = train_linreg(grid, X, y, lr=0.05, steps=200,
                            merge_every=16)
        err_base = float(np.linalg.norm(np.asarray(base.w) - w_star))
        assert err <= 1.5 * err_base + 0.05, (err, err_base)

    def test_with_compression_ef_stays_congruent(self):
        """Adaptive rounds always run the state wire, so the EF buffer
        keeps one shape while k changes under it."""
        grid, X, y = self._problem()
        holder: dict = {}
        res = train_linreg(grid, X, y, lr=0.05, steps=90,
                           merge_plan=MergePlan(
                               compression=INT8,
                               outer=AdaptiveCadence(k_max=4)),
                           merge_state=holder)
        assert "error" in holder and "cadence_trace" in holder
        assert len(res.history) == 90

    def test_controller_validation(self):
        with pytest.raises(ValueError, match="growth"):
            AdaptiveCadence(growth=1)

    def test_starting_cadence_from_plan(self):
        grid, X, y = self._problem()
        holder: dict = {}
        train_linreg(grid, X, y, lr=0.05, steps=32,
                     merge_plan=MergePlan(cadence=4,
                                          outer=AdaptiveCadence(
                                              k_max=8)),
                     merge_state=holder)
        assert holder["cadence_trace"][0] == 4


class TestDtreeFallbackWarning:
    def test_cadence_warns_once_per_fit(self):
        X, y = datasets.mixture_classification(KEY, 600, 6, 2)
        grid = make_cpu_grid(8)
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            train_dtree(grid, X, y, max_depth=3, merge_every=4)
        fallbacks = [w for w in rec
                     if issubclass(w.category, MergeFallbackWarning)]
        assert len(fallbacks) == 1
        assert "merge_every=4" in str(fallbacks[0].message)

    def test_pipeline_flags_warn(self):
        X, y = datasets.mixture_classification(KEY, 600, 6, 2)
        grid = make_cpu_grid(8)
        with pytest.warns(MergeFallbackWarning, match="overlap"):
            train_dtree(grid, X, y, max_depth=3, overlap_merge=True)
        with pytest.warns(MergeFallbackWarning, match="SlowMo"):
            train_dtree(grid, X, y, max_depth=3,
                        merge_plan=MergePlan(outer=SlowMo()))

    def test_mixed_spellings_rejected(self):
        """dtree must refuse conflicting spellings like every other
        entry point — not silently drop the legacy kwargs."""
        X, y = datasets.mixture_classification(KEY, 200, 4, 2)
        grid = make_cpu_grid(4)
        with pytest.raises(ValueError, match="not both"):
            train_dtree(grid, X, y, max_depth=2, merge_every=4,
                        merge_plan=MergePlan())

    def test_default_is_silent(self):
        X, y = datasets.mixture_classification(KEY, 600, 6, 2)
        grid = make_cpu_grid(8)
        with warnings.catch_warnings():
            warnings.simplefilter("error", MergeFallbackWarning)
            train_dtree(grid, X, y, max_depth=3)

    def test_fallback_result_identical_to_default(self):
        X, y = datasets.mixture_classification(KEY, 600, 6, 2)
        grid = make_cpu_grid(8)
        r0 = train_dtree(grid, X, y, max_depth=3)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", MergeFallbackWarning)
            r1 = train_dtree(grid, X, y, max_depth=3, merge_every=4)
        np.testing.assert_array_equal(np.asarray(r0.tree.feature),
                                      np.asarray(r1.tree.feature))
        np.testing.assert_array_equal(np.asarray(r0.tree.threshold),
                                      np.asarray(r1.tree.threshold))


class TestOuterOptimizerInterface:
    def test_plans_hash_into_cache_keys(self):
        assert MergePlan(cadence=4) == MergePlan(cadence=4)
        assert hash(SlowMo(beta=0.5)) == hash(SlowMo(beta=0.5))
        assert SlowMo(beta=0.5) != SlowMo(beta=0.9)

    def test_custom_outer_optimizer_runs(self):
        """The interface is open: a half-step commit (a trivial custom
        outer) threads through the executor — and overriding ``commit``
        flips ``plain_commit`` automatically, so a forgotten flag can't
        silently route the plan around the custom commit."""

        @dataclasses.dataclass(frozen=True)
        class HalfStep(OuterOptimizer):
            def init(self, state):
                return ()

            def commit(self, anchor, delta, buf):
                return jax.tree.map(lambda a, d: a + 0.5 * d,
                                    anchor, delta), buf

        assert not HalfStep.plain_commit      # derived, not declared
        X, y, _ = datasets.regression(KEY, 320, 6)
        grid = make_cpu_grid(4)
        res = train_linreg(grid, X, y, lr=0.05, steps=40,
                           merge_plan=MergePlan(cadence=4,
                                                outer=HalfStep()))
        assert len(res.history) == 40
        assert np.all(np.isfinite(np.asarray(res.w)))
        # ...and it actually steered the trajectory (half-strength
        # commits land elsewhere than the plain average)
        r_avg = train_linreg(grid, X, y, lr=0.05, steps=40,
                             merge_every=4)
        assert not np.array_equal(np.asarray(res.w),
                                  np.asarray(r_avg.w))

    def test_average_commit_is_plain(self):
        assert AverageCommit().plain_commit
        assert AdaptiveCadence().plain_commit
        assert not SlowMo().plain_commit


class TestNesterovOracle:
    """The Nesterov outer optimizer (ROADMAP "Next") against a
    hand-rolled numpy replica at cadence 1 and 4, mirroring the SlowMo
    oracle: m' = beta*m + g, w' = w - alpha*(g + beta*m') with the
    negated merge delta as pseudo-gradient g."""

    BETA, ALPHA = 0.5, 1.0

    def _setup(self):
        V, per, d, lr = 4, 32, 6, 0.05
        X = np.asarray(jax.random.normal(KEY, (V * per, d)), np.float32)
        w_true = np.linspace(-1.0, 1.0, d).astype(np.float32)
        y = X @ w_true
        return V, per, d, lr, X, y

    def _commit(self, w, proposed, m):
        g = -(proposed - w)
        m = self.BETA * m + g
        return (w - self.ALPHA * (g + self.BETA * m)).astype(np.float32), m

    def _oracle_cadence1(self, V, per, d, lr, X, y, steps):
        n = V * per
        w = np.zeros((d,), np.float32)
        m = np.zeros((d,), np.float32)
        for _ in range(steps):
            g = np.zeros((d,), np.float32)
            for v in range(V):
                Xv, yv = X[v * per:(v + 1) * per], y[v * per:(v + 1) * per]
                g += (Xv.T @ (Xv @ w - yv)).astype(np.float32)
            proposed = w - lr * g / n
            w, m = self._commit(w, proposed, m)
        return w

    def _oracle_cadence_k(self, V, per, d, lr, X, y, steps, k):
        n = V * per
        w = np.zeros((d,), np.float32)
        m = np.zeros((d,), np.float32)
        done = 0
        while done < steps:
            kk = min(k, steps - done)
            lanes = []
            for v in range(V):
                Xv, yv = X[v * per:(v + 1) * per], y[v * per:(v + 1) * per]
                wv = w.copy()
                for _ in range(kk):
                    g = V * (Xv.T @ (Xv @ wv - yv)).astype(np.float32)
                    wv = wv - lr * g / n
                lanes.append(wv)
            avg = np.mean(lanes, axis=0).astype(np.float32)
            w, m = self._commit(w, avg, m)
            done += kk
        return w

    def test_cadence1_matches_oracle(self):
        from repro.distributed.merge_plan import Nesterov
        V, per, d, lr, X, y = self._setup()
        grid = make_cpu_grid(V)
        res = train_linreg(grid, jnp.asarray(X), jnp.asarray(y), lr=lr,
                           steps=200, merge_plan=MergePlan(
                               outer=Nesterov(beta=self.BETA,
                                              outer_lr=self.ALPHA)))
        w_oracle = self._oracle_cadence1(V, per, d, lr, X, y, 200)
        np.testing.assert_allclose(np.asarray(res.w), w_oracle,
                                   rtol=1e-3, atol=1e-3)

    def test_cadence4_matches_oracle(self):
        from repro.distributed.merge_plan import Nesterov
        V, per, d, lr, X, y = self._setup()
        grid = make_cpu_grid(V)
        res = train_linreg(grid, jnp.asarray(X), jnp.asarray(y), lr=lr,
                           steps=200, merge_plan=MergePlan(
                               cadence=4,
                               outer=Nesterov(beta=self.BETA,
                                              outer_lr=self.ALPHA)))
        w_oracle = self._oracle_cadence_k(V, per, d, lr, X, y, 200, 4)
        np.testing.assert_allclose(np.asarray(res.w), w_oracle,
                                   rtol=1e-3, atol=1e-3)

    def test_beta0_alpha1_recovers_average(self):
        from repro.distributed.merge_plan import Nesterov
        V, per, d, lr, X, y = self._setup()
        grid = make_cpu_grid(V)
        r_avg = train_linreg(grid, jnp.asarray(X), jnp.asarray(y),
                             lr=lr, steps=40, merge_every=4)
        r_nag = train_linreg(grid, jnp.asarray(X), jnp.asarray(y),
                             lr=lr, steps=40, merge_plan=MergePlan(
                                 cadence=4, outer=Nesterov(
                                     beta=0.0, outer_lr=1.0)))
        np.testing.assert_allclose(np.asarray(r_nag.w),
                                   np.asarray(r_avg.w),
                                   rtol=1e-5, atol=1e-6)

    def test_momentum_continues_across_fits(self):
        from repro.distributed.merge_plan import Nesterov
        X, y, _ = datasets.regression(KEY, 320, 6)
        grid = make_cpu_grid(4)
        data, n, lf, uf, w0 = make_linreg_step(grid, X, y, lr=0.05)
        plan = MergePlan(cadence=4, outer=Nesterov(beta=0.5))
        w_one, _ = grid.fit(init_state=w0, local_fn=lf, update_fn=uf,
                            data=data, steps=96, merge_plan=plan)
        holder: dict = {}
        w_half, _ = grid.fit(init_state=w0, local_fn=lf, update_fn=uf,
                             data=data, steps=48, merge_plan=plan,
                             merge_state=holder)
        assert "momentum" in holder
        w_two, _ = grid.fit(init_state=w_half, local_fn=lf,
                            update_fn=uf, data=data, steps=48,
                            merge_plan=plan, merge_state=holder)
        np.testing.assert_allclose(np.asarray(w_two), np.asarray(w_one),
                                   rtol=1e-6, atol=1e-7)

    def test_not_plain_and_config_spelling(self):
        from repro.distributed.merge_plan import Nesterov
        from repro.configs.pim_ml import PimMLConfig
        assert not Nesterov().plain_commit
        plan = PimMLConfig(merge_outer="nesterov",
                           merge_every=4).merge_plan()
        assert isinstance(plan.outer, Nesterov)
