"""The Workload subsystem (``core.mlalgos.api``): protocol bit-exactness
for the four ported estimators, capability-flag degradation, compile-
cache stability of bound Programs, the two new PIM-Opt estimators
(linear SVM, multinomial logreg) against numpy oracles under cadence
and minibatching, and the Trainer integration."""

import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import datasets, make_cpu_grid
from repro.core.mlalgos import (api, WORKLOADS, LinReg, LogReg, KMeans,
                                DecisionTree, LinearSVM,
                                MultinomialLogReg, train_linreg,
                                train_svm, train_multinomial)
from repro.core.mlalgos.svm import svm_accuracy
from repro.core.mlalgos.multinomial import multinomial_accuracy
from repro.distributed.merge_plan import (MergePlan, SlowMo,
                                          MergeFallbackWarning)
from repro.runtime import Trainer, TrainerConfig

KEY = jax.random.PRNGKey(0)

# estimator, fit options and data kind of each refit case
REFIT_CASES = {
    "logreg-int8-lut": (LogReg(precision="int8", sigmoid="lut"), {},
                        "binary"),
    "logreg-int8-lut-b8": (LogReg(precision="int8", sigmoid="lut"),
                           {"batch_size": 8}, "binary"),
    "logreg-fp32-lut": (LogReg(sigmoid="lut"), {}, "binary"),
    "logreg-fp32-lut-b8": (LogReg(sigmoid="lut"), {"batch_size": 8},
                           "binary"),
    "linreg-int8": (LinReg(lr=0.05, precision="int8"), {}, "regression"),
    "svm-int8": (LinearSVM(precision="int8"), {}, "binary"),
    "multinomial-int8-lut": (MultinomialLogReg(
        n_classes=3, precision="int8", softmax="lut"), {}, "classes"),
    "kmeans-int8": (KMeans(k=3, precision="int8"), {}, "blobs"),
}


def _refit_case(case):
    """``(workload, fit kwargs, X, y)`` of one refit case, 256 x 6."""
    wl, kw, kind = REFIT_CASES[case]
    if kind == "regression":
        X, y, _ = datasets.regression(KEY, 256, 6)
    elif kind == "classes":
        X, y = datasets.mixture_classification(KEY, 256, 6, 3)
    elif kind == "blobs":
        X, _, _ = datasets.blobs(KEY, 256, 6, 3)
        y = None
    else:
        X, y, _ = datasets.binary_classification(KEY, 256, 6)
    return wl, kw, X, y


class TestProtocolBitExactness:
    """batch_size=None through api.fit must be bit-exact with the
    python-engine oracle (i.e. with the PR 4 engine) for every ported
    workload."""

    def test_linreg(self):
        X, y, _ = datasets.regression(KEY, 400, 8)
        grid = make_cpu_grid(8)
        r = api.fit(LinReg(lr=0.05), grid, X, y, steps=40)
        r_py = api.fit(LinReg(lr=0.05), grid, X, y, steps=40,
                       engine="python")
        np.testing.assert_array_equal(np.asarray(r.state),
                                      np.asarray(r_py.state))

    def test_logreg(self):
        X, y, _ = datasets.binary_classification(KEY, 400, 6)
        grid = make_cpu_grid(8)
        wl = LogReg(lr=0.5, sigmoid="lut")
        r = api.fit(wl, grid, X, y, steps=30)
        r_py = api.fit(wl, grid, X, y, steps=30, engine="python")
        np.testing.assert_array_equal(np.asarray(r.state),
                                      np.asarray(r_py.state))

    def test_kmeans(self):
        X, _, _ = datasets.blobs(KEY, 500, 4, k=3, spread=0.3)
        grid = make_cpu_grid(8)
        r = api.fit(KMeans(k=3), grid, X, steps=8)
        r_py = api.fit(KMeans(k=3), grid, X, steps=8, engine="python")
        np.testing.assert_array_equal(np.asarray(r.state),
                                      np.asarray(r_py.state))

    def test_svm(self):
        X, y, _ = datasets.binary_classification(KEY, 400, 6)
        grid = make_cpu_grid(8)
        r = api.fit(LinearSVM(lr=0.1), grid, X, y, steps=30)
        r_py = api.fit(LinearSVM(lr=0.1), grid, X, y, steps=30,
                       engine="python")
        np.testing.assert_array_equal(np.asarray(r.state),
                                      np.asarray(r_py.state))

    def test_multinomial(self):
        X, y = datasets.mixture_classification(KEY, 400, 6, 3)
        grid = make_cpu_grid(8)
        wl = MultinomialLogReg(n_classes=3)
        r = api.fit(wl, grid, X, y, steps=30)
        r_py = api.fit(wl, grid, X, y, steps=30, engine="python")
        np.testing.assert_array_equal(np.asarray(r.state),
                                      np.asarray(r_py.state))

    def test_dtree_via_api_matches_wrapper(self):
        from repro.core.mlalgos.dtree import dtree_predict, train_dtree
        X, y = datasets.mixture_classification(KEY, 600, 6, 2)
        grid = make_cpu_grid(8)
        r_api = api.fit(DecisionTree(max_depth=3), grid, X, y, steps=3)
        r_wrap = train_dtree(grid, X, y, max_depth=3)
        np.testing.assert_array_equal(
            np.asarray(dtree_predict(r_api.state, X)),
            np.asarray(dtree_predict(r_wrap.tree, X)))


class TestMergeCaps:
    def test_default_supports_everything(self):
        caps = api.MergeCaps()
        plan = MergePlan(cadence=4, overlap=True)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            out_plan, bs = caps.constrain("x", plan, 16)
        assert out_plan is plan and bs == 16

    def test_exact_only_degrades_everything_with_one_warning(self):
        caps = api.MergeCaps.exact_only("discrete commits")
        plan = MergePlan(cadence=4, overlap=True, outer=SlowMo())
        with pytest.warns(MergeFallbackWarning) as rec:
            out_plan, bs = caps.constrain("dtree", plan, 16)
        assert len(rec) == 1
        msg = str(rec[0].message)
        assert "merge_every=4" in msg and "overlap_merge" in msg
        assert "outer=SlowMo" in msg and "batch_size=16" in msg
        assert out_plan.is_exact_default and out_plan.cadence == 1
        assert bs is None

    def test_dtree_minibatch_degrades(self):
        X, y = datasets.mixture_classification(KEY, 400, 4, 2)
        grid = make_cpu_grid(4)
        with pytest.warns(MergeFallbackWarning, match="batch_size"):
            api.fit(DecisionTree(max_depth=2), grid, X, y, steps=2,
                    batch_size=8)


class TestProgramCaching:
    def test_repeated_fit_on_program_reuses_runner(self):
        X, y, _ = datasets.regression(KEY, 256, 6)
        grid = make_cpu_grid(4)
        program = LinReg(lr=0.05).bind(grid, X, y)
        program.fit(steps=4)
        n0 = len(grid._fit_cache)
        program.fit(steps=4)
        program.fit(steps=4, batch_size=8)
        n1 = len(grid._fit_cache)
        program.fit(steps=4, batch_size=8)
        assert len(grid._fit_cache) == n1 > n0

    def test_rebinding_equal_config_shares_runner(self):
        """Two equal fp32 estimator configs (hashable dataclass +
        primitive consts) must share a compiled runner across binds —
        the train_* rebuild-per-call pattern."""
        X, y, _ = datasets.regression(KEY, 256, 6)
        grid = make_cpu_grid(4)
        LinReg(lr=0.05).bind(grid, X, y).fit(steps=4)
        n0 = len(grid._fit_cache)
        LinReg(lr=0.05).bind(grid, X, y).fit(steps=4)
        assert len(grid._fit_cache) == n0

    def test_different_hyperparams_do_not_collide(self):
        X, y, _ = datasets.regression(KEY, 256, 6)
        grid = make_cpu_grid(4)
        r1 = api.fit(LinReg(lr=0.1), grid, X, y, steps=30)
        r2 = api.fit(LinReg(lr=0.01), grid, X, y, steps=30)
        assert float(jnp.max(jnp.abs(r1.state - r2.state))) > 1e-6

    @pytest.mark.parametrize("case", sorted(REFIT_CASES))
    def test_second_fit_adds_no_runner(self, case):
        """Every ``api.fit`` binds anew (a new ``prepare``, new scale
        arrays, a new sigmoid lookup, a new minibatch wrapper), and a
        second fit of an equal estimator on the same grid still finds
        its runner in the cache."""
        wl, kw, X, y = _refit_case(case)
        grid = make_cpu_grid(4)
        api.fit(wl, grid, X, y, steps=4, **kw)
        n = len(grid._fit_cache)
        api.fit(wl, grid, X, y, steps=4, **kw)
        assert len(grid._fit_cache) == n

    @pytest.mark.parametrize("case", sorted(REFIT_CASES))
    def test_new_scales_share_runner_bit_exact(self, case):
        """The quantization scales are a runner operand, not a trace
        constant: a dataset of the same shape with other scales reuses
        the runner, and each fit equals the fit on a fresh grid bit for
        bit."""
        wl, kw, X, y = _refit_case(case)
        X2 = X * jnp.linspace(0.5, 3.0, X.shape[1], dtype=X.dtype)
        grid = make_cpu_grid(4)
        fits = [api.fit(wl, grid, X, y, steps=4, **kw)]
        n = len(grid._fit_cache)
        fits.append(api.fit(wl, grid, X2, y, steps=4, **kw))
        assert len(grid._fit_cache) == n
        for res, data in zip(fits, (X, X2)):
            fresh = api.fit(wl, make_cpu_grid(4), data, y, steps=4, **kw)
            for a, b in zip(jax.tree.leaves(res.state),
                            jax.tree.leaves(fresh.state)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b))
            for h, g in zip(res.history, fresh.history):
                for a, b in zip(jax.tree.leaves(h), jax.tree.leaves(g)):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))
        if wl.precision != "fp32":
            scales = [wl.prepare(grid, d, y)[2]["x_scale"] for d in (X, X2)]
            assert not np.array_equal(*map(np.asarray, scales))

    def test_fit_result_eval(self):
        X, y, _ = datasets.binary_classification(KEY, 400, 6)
        grid = make_cpu_grid(4)
        res = api.fit(LogReg(lr=0.5), grid, X, y, steps=40)
        assert 0.5 <= res.eval(X, y)["accuracy"] <= 1.0


def _svm_oracle(X, y, lr, l2, steps):
    """Full-batch hinge subgradient descent, global-sum formulation."""
    ys = np.where(np.asarray(y) > 0, 1.0, -1.0).astype(np.float32)
    n, d = X.shape
    w = np.zeros((d,), np.float32)
    for _ in range(steps):
        z = X @ w
        active = ((ys * z) < 1.0).astype(np.float32)
        g = X.T @ (-(ys * active))
        w = (w - lr * (g / n + l2 * w)).astype(np.float32)
    return w


def _multinomial_oracle(X, y, C, lr, steps):
    n, d = X.shape
    W = np.zeros((d, C), np.float32)
    onehot = np.eye(C, dtype=np.float32)[np.asarray(y)]
    for _ in range(steps):
        Z = X @ W
        Z = Z - Z.max(axis=1, keepdims=True)
        P = np.exp(Z)
        P /= P.sum(axis=1, keepdims=True)
        G = X.T @ (P - onehot)
        W = (W - lr * G / n).astype(np.float32)
    return W


class TestLinearSVM:
    def _data(self):
        return datasets.binary_classification(KEY, 2048, 10)

    def test_matches_numpy_oracle(self):
        X, y, _ = self._data()
        grid = make_cpu_grid(8)
        res = train_svm(grid, X, y, lr=0.1, l2=1e-3, steps=100)
        w_o = _svm_oracle(np.asarray(X), np.asarray(y), 0.1, 1e-3, 100)
        np.testing.assert_allclose(np.asarray(res.w), w_o, rtol=1e-3,
                                   atol=1e-4)

    def test_oracle_matching_accuracy_cadence_minibatch(self):
        """The acceptance grid: MergePlan cadence {1,4} x minibatch —
        the trained model must match the full-batch oracle's accuracy
        to within 2 points."""
        X, y, _ = self._data()
        grid = make_cpu_grid(8)
        w_o = _svm_oracle(np.asarray(X), np.asarray(y), 0.1, 1e-3, 150)
        acc_o = svm_accuracy(jnp.asarray(w_o), X, y)
        for k in (1, 4):
            res = train_svm(grid, X, y, lr=0.1, l2=1e-3, steps=150,
                            merge_plan=MergePlan(cadence=k),
                            batch_size=64)
            acc = svm_accuracy(res.w, X, y)
            assert acc >= acc_o - 0.02, (k, acc, acc_o)

    def test_int8_parity(self):
        X, y, _ = self._data()
        grid = make_cpu_grid(8)
        r32 = train_svm(grid, X, y, lr=0.1, steps=100)
        r8 = train_svm(grid, X, y, lr=0.1, steps=100, precision="int8")
        a32, a8 = svm_accuracy(r32.w, X, y), svm_accuracy(r8.w, X, y)
        assert abs(a32 - a8) < 0.02

    def test_pm1_labels_accepted(self):
        X, y, _ = self._data()
        ypm = jnp.where(y > 0, 1.0, -1.0)
        grid = make_cpu_grid(8)
        r01 = train_svm(grid, X, y, lr=0.1, steps=40)
        rpm = train_svm(grid, X, ypm, lr=0.1, steps=40)
        np.testing.assert_array_equal(np.asarray(r01.w),
                                      np.asarray(rpm.w))


class TestMultinomial:
    def _data(self, C=4):
        return datasets.mixture_classification(KEY, 2048, 10, C)

    def test_matches_numpy_oracle(self):
        X, y = self._data()
        grid = make_cpu_grid(8)
        res = train_multinomial(grid, X, y, n_classes=4, lr=0.5,
                                steps=80)
        W_o = _multinomial_oracle(np.asarray(X), np.asarray(y), 4, 0.5,
                                  80)
        np.testing.assert_allclose(np.asarray(res.W), W_o, rtol=1e-3,
                                   atol=1e-4)

    def test_oracle_matching_accuracy_cadence_minibatch(self):
        X, y = self._data()
        grid = make_cpu_grid(8)
        W_o = _multinomial_oracle(np.asarray(X), np.asarray(y), 4, 0.5,
                                  120)
        acc_o = multinomial_accuracy(jnp.asarray(W_o), X, y)
        for k in (1, 4):
            res = train_multinomial(grid, X, y, n_classes=4, lr=0.5,
                                    steps=120,
                                    merge_plan=MergePlan(cadence=k),
                                    batch_size=64)
            acc = multinomial_accuracy(res.W, X, y)
            assert acc >= acc_o - 0.02, (k, acc, acc_o)

    def test_lut_softmax_parity(self):
        """Insight I2 for the C-class case: the exp-LUT softmax costs
        ~no accuracy vs the exact softmax."""
        X, y = self._data()
        grid = make_cpu_grid(8)
        r_e = train_multinomial(grid, X, y, n_classes=4, lr=0.5,
                                steps=80, softmax="exact")
        r_l = train_multinomial(grid, X, y, n_classes=4, lr=0.5,
                                steps=80, softmax="lut")
        a_e = multinomial_accuracy(r_e.W, X, y)
        a_l = multinomial_accuracy(r_l.W, X, y)
        assert abs(a_e - a_l) < 0.01
        assert a_e > 0.8

    def test_int8_parity(self):
        X, y = self._data()
        grid = make_cpu_grid(8)
        r32 = train_multinomial(grid, X, y, n_classes=4, lr=0.5,
                                steps=60)
        r8 = train_multinomial(grid, X, y, n_classes=4, lr=0.5,
                               steps=60, precision="int8")
        a32 = multinomial_accuracy(r32.W, X, y)
        a8 = multinomial_accuracy(r8.W, X, y)
        assert abs(a32 - a8) < 0.03

    def test_two_classes_agrees_with_binary_logreg_direction(self):
        """Sanity: C=2 multinomial separates like the binary model."""
        X, y, _ = datasets.binary_classification(KEY, 1024, 8)
        grid = make_cpu_grid(8)
        res = train_multinomial(grid, X, y.astype(jnp.int32),
                                n_classes=2, lr=0.5, steps=80)
        assert multinomial_accuracy(res.W, X, y) > 0.7


class TestRegistryAndConfig:
    def test_workload_registry_complete(self):
        assert set(WORKLOADS) == {"linreg", "logreg", "kmeans", "dtree",
                                  "svm", "multinomial"}
        for cls in WORKLOADS.values():
            assert issubclass(cls, api.Workload)

    def test_config_workload_spec(self):
        from repro.configs.pim_ml import PimMLConfig
        wl = PimMLConfig(workload="svm").workload_spec()
        assert isinstance(wl, LinearSVM)
        wl = PimMLConfig(workload="multinomial").workload_spec("int8")
        assert isinstance(wl, MultinomialLogReg)
        assert wl.softmax == "lut"
        with pytest.raises(ValueError, match="workload"):
            PimMLConfig(workload="nope").workload_spec()

    def test_spec_fns_lowerable(self):
        """The dryrun path: spec-level fns trace over ShapeDtypeStructs
        without any resident data."""
        for wl in (LogReg(precision="int8", sigmoid="lut"),
                   LinearSVM(precision="int8"),
                   MultinomialLogReg(n_classes=4, precision="int8",
                                     softmax="lut")):
            lf, uf, s0, consts = wl.spec_fns(features=8, rows=64)
            sl = {"X": jax.ShapeDtypeStruct((16, 8), jnp.int8),
                  "y0": jax.ShapeDtypeStruct(
                      (16,), jnp.int32 if wl.name == "multinomial"
                      else jnp.float32),
                  "w": jax.ShapeDtypeStruct((16,), jnp.float32)}
            part = jax.eval_shape(lf, s0, sl, consts)
            out = jax.eval_shape(uf, s0, part, consts)
            assert jax.tree.map(lambda x: x.shape, out[0]) \
                == jax.tree.map(lambda x: x.shape,
                                jax.eval_shape(lambda: s0))


class TestTrainerIntegration:
    def test_for_program_runs_and_resumes(self, tmp_path):
        X, y, _ = datasets.regression(KEY, 512, 8)
        grid = make_cpu_grid(8)
        program = LinReg(lr=0.05).bind(grid, X, y)
        cfg = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=10,
                            log_every=5, batch_size=16)
        tr = Trainer.for_program(program, cfg)
        tr.run(30)
        assert float(tr.state[1]) == 30.0       # sampler counter rides
        tr2 = Trainer.for_program(program, cfg)  # resume from ckpt
        assert tr2.start_step == 30
        assert float(tr2.state[1]) == 30.0      # schedule position too

    def test_for_program_full_batch_matches_fit(self):
        X, y, _ = datasets.regression(KEY, 512, 8)
        grid = make_cpu_grid(8)
        program = LinReg(lr=0.05).bind(grid, X, y)
        tr = Trainer.for_program(program)
        tr.run(20)
        res = program.fit(steps=20)
        np.testing.assert_allclose(np.asarray(tr.state),
                                   np.asarray(res.state), rtol=1e-6)

    def test_for_program_cadence_matches_fit(self):
        """Cadence plans drive round-granular dispatch: bit-compatible
        final state with api.fit at the same cadence, one history entry
        per local step (remainder round included)."""
        X, y, _ = datasets.regression(KEY, 256, 6)
        grid = make_cpu_grid(4)
        program = LinReg(lr=0.05).bind(grid, X, y)
        tr = Trainer.for_program(program, TrainerConfig(merge_every=4))
        out = tr.run(10)                 # 2 full rounds + remainder 2
        res = program.fit(steps=10, merge_every=4)
        np.testing.assert_allclose(np.asarray(tr.state),
                                   np.asarray(res.state), rtol=1e-6)
        assert [e["step"] for e in out["history"]] == list(range(10))
        assert all("loss" in e for e in out["history"])

    def test_for_program_cadence_ckpt_on_merge_boundary(self, tmp_path):
        """ckpt_every that lands mid-round defers to the next merge
        boundary; resume restores the deferred step."""
        X, y, _ = datasets.regression(KEY, 256, 6)
        grid = make_cpu_grid(4)
        program = LinReg(lr=0.05).bind(grid, X, y)
        cfg = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=5,
                            merge_plan=MergePlan(cadence=4),
                            log_every=100)
        tr = Trainer.for_program(program, cfg)
        tr.run(16)       # merge boundaries at steps 3, 7, 11, 15
        steps = {int(p.name.split("_")[1])
                 for p in tmp_path.iterdir() if p.name.startswith("step_")}
        assert steps and all((s + 1) % 4 == 0 for s in steps)
        tr2 = Trainer.for_program(program, cfg)
        assert tr2.start_step == 16

    def test_for_program_refuses_pipeline_plans(self):
        from repro.distributed.compression import CompressionConfig
        X, y, _ = datasets.regression(KEY, 256, 6)
        grid = make_cpu_grid(4)
        program = LinReg(lr=0.05).bind(grid, X, y)
        for plan in (MergePlan(cadence=2,
                               compression=CompressionConfig(bits=8)),
                     MergePlan(outer=SlowMo()),
                     "auto"):
            with pytest.raises(ValueError, match="exact merge rounds"):
                Trainer.for_program(
                    program, TrainerConfig(merge_plan=plan))
