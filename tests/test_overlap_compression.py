"""The overlapped + compressed merge pipeline
(``fit(overlap_merge=..., merge_compression=...)``).

Contracts pinned here:
  * both flags off reproduces the PR 2 engine bit-exactly for all four
    mlalgos (it takes the unmodified code path),
  * int8 + error-feedback merges match a hand-rolled numpy oracle and
    converge to within rtol of exact merges over 200 steps,
  * the overlap pipeline (one-round staleness) matches its python-engine
    oracle bit-exactly and converges within tolerance,
  * integer-dtype leaves pass through the compressed reduce uncompressed,
  * the error-feedback buffer continues across ``fit`` calls via the
    ``merge_state`` holder and across Trainer restarts via checkpoints.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core import datasets, make_cpu_grid
from repro.core.mlalgos import (make_linreg_step, train_linreg,
                                train_logreg, train_kmeans, train_dtree)
from repro.core.mlalgos.linreg import closed_form
from repro.distributed import compression as comp
from repro.distributed.compression import CompressionConfig
from repro.runtime import Trainer, TrainerConfig

KEY = jax.random.PRNGKey(0)
INT8 = CompressionConfig(bits=8)


class TestDefaultsBitExact:
    """overlap_merge=False + merge_compression=None must be the PR 2
    engine — asserted against the python-loop oracle for all four
    mlalgos, with the flags passed explicitly."""

    def test_linreg(self):
        X, y, _ = datasets.regression(KEY, 400, 8)
        grid = make_cpu_grid(8)
        r = train_linreg(grid, X, y, lr=0.05, steps=40,
                         overlap_merge=False, merge_compression=None)
        r_py = train_linreg(grid, X, y, lr=0.05, steps=40,
                            engine="python")
        np.testing.assert_array_equal(np.asarray(r.w), np.asarray(r_py.w))

    def test_logreg(self):
        X, y, _ = datasets.binary_classification(KEY, 400, 6)
        grid = make_cpu_grid(8)
        r = train_logreg(grid, X, y, lr=0.5, steps=30,
                         overlap_merge=False, merge_compression=None)
        r_py = train_logreg(grid, X, y, lr=0.5, steps=30,
                            engine="python")
        np.testing.assert_array_equal(np.asarray(r.w), np.asarray(r_py.w))

    def test_kmeans(self):
        X, _, _ = datasets.blobs(KEY, 500, 4, k=3, spread=0.3)
        grid = make_cpu_grid(8)
        r = train_kmeans(grid, X, 3, iters=8, overlap_merge=False,
                         merge_compression=None)
        r_py = train_kmeans(grid, X, 3, iters=8, engine="python")
        np.testing.assert_array_equal(np.asarray(r.centroids),
                                      np.asarray(r_py.centroids))

    def test_dtree_flags_inert(self):
        X, y = datasets.mixture_classification(KEY, 600, 6, 2)
        grid = make_cpu_grid(8)
        r0 = train_dtree(grid, X, y, max_depth=3)
        r1 = train_dtree(grid, X, y, max_depth=3, overlap_merge=True,
                         merge_compression=INT8)
        np.testing.assert_array_equal(np.asarray(r0.tree.feature),
                                      np.asarray(r1.tree.feature))
        np.testing.assert_array_equal(np.asarray(r0.tree.threshold),
                                      np.asarray(r1.tree.threshold))

    def test_cadence_with_flags_off_bit_exact(self):
        X, y, _ = datasets.regression(KEY, 300, 5)
        grid = make_cpu_grid(4)
        r = train_linreg(grid, X, y, lr=0.05, steps=12, merge_every=4,
                         overlap_merge=False, merge_compression=None)
        r_py = train_linreg(grid, X, y, lr=0.05, steps=12, merge_every=4,
                            engine="python")
        np.testing.assert_array_equal(np.asarray(r.w), np.asarray(r_py.w))


def _ef_quantize_np(target, bits=8):
    qmax = 2 ** (bits - 1) - 1
    amax = np.max(np.abs(target))
    scale = max(amax, 1e-12) / qmax
    q = np.clip(np.round(target / scale), -qmax - 1, qmax)
    deq = (q * scale).astype(np.float32)
    return deq, target - deq


class TestEFConvergenceOracle:
    """int8 + error feedback on the cadence-1 gradient wire: a numpy
    replica of the quantized merge converges to within rtol of exact
    merges over 200 steps, and the jax engine matches the replica."""

    def _setup(self):
        V, per, d, lr = 4, 32, 6, 0.05
        X = np.asarray(jax.random.normal(KEY, (V * per, d)), np.float32)
        w_true = np.linspace(-1.0, 1.0, d).astype(np.float32)
        y = X @ w_true
        return V, per, d, lr, X, y

    def _oracle(self, V, per, d, lr, X, y, steps, compressed):
        n = V * per
        w = np.zeros((d,), np.float32)
        e_g = np.zeros((d,), np.float32)
        e_l = np.zeros((), np.float32)
        for _ in range(steps):
            g = np.zeros((d,), np.float32)
            loss = np.float32(0.0)
            for v in range(V):
                Xv, yv = X[v * per:(v + 1) * per], y[v * per:(v + 1) * per]
                r = Xv @ w - yv
                g += (Xv.T @ r).astype(np.float32)
                loss += np.float32(np.sum(r * r))
            if compressed:
                g, e_g = _ef_quantize_np(g + e_g)
                loss, e_l = _ef_quantize_np(loss + e_l)
            w = w - lr * g / n
        return w

    def test_int8_ef_converges_within_rtol_of_exact(self):
        V, per, d, lr, X, y = self._setup()
        steps = 200
        w_exact = self._oracle(V, per, d, lr, X, y, steps, False)
        w_comp = self._oracle(V, per, d, lr, X, y, steps, True)
        # error feedback keeps the compressed iterates O(1) from exact
        np.testing.assert_allclose(w_comp, w_exact, rtol=5e-3, atol=5e-3)

    def test_engine_matches_numpy_oracle(self):
        V, per, d, lr, X, y = self._setup()
        steps = 200
        grid = make_cpu_grid(V)
        res = train_linreg(grid, jnp.asarray(X), jnp.asarray(y), lr=lr,
                           steps=steps, merge_compression=INT8)
        w_oracle = self._oracle(V, per, d, lr, X, y, steps, True)
        np.testing.assert_allclose(np.asarray(res.w), w_oracle,
                                   rtol=1e-3, atol=1e-3)

    def test_engine_compressed_close_to_engine_exact(self):
        V, per, d, lr, X, y = self._setup()
        grid = make_cpu_grid(V)
        r_exact = train_linreg(grid, jnp.asarray(X), jnp.asarray(y),
                               lr=lr, steps=200)
        r_comp = train_linreg(grid, jnp.asarray(X), jnp.asarray(y),
                              lr=lr, steps=200, merge_compression=INT8)
        np.testing.assert_allclose(np.asarray(r_comp.w),
                                   np.asarray(r_exact.w),
                                   rtol=5e-3, atol=5e-3)

    def test_no_error_feedback_biases_more(self):
        """EF exists for a reason: with it the compressed run lands
        closer to exact than without it (stateless quantization)."""
        V, per, d, lr, X, y = self._setup()
        grid = make_cpu_grid(V)
        w_exact = np.asarray(train_linreg(
            grid, jnp.asarray(X), jnp.asarray(y), lr=lr, steps=200).w)
        w_ef = np.asarray(train_linreg(
            grid, jnp.asarray(X), jnp.asarray(y), lr=lr, steps=200,
            merge_compression=INT8).w)
        w_noef = np.asarray(train_linreg(
            grid, jnp.asarray(X), jnp.asarray(y), lr=lr, steps=200,
            merge_compression=CompressionConfig(
                bits=8, error_feedback=False)).w)
        assert np.linalg.norm(w_ef - w_exact) <= \
            np.linalg.norm(w_noef - w_exact) + 1e-6


class TestOverlapPipeline:
    def test_scan_matches_python_engine(self):
        X, y, _ = datasets.regression(KEY, 400, 8)
        grid = make_cpu_grid(8)
        for kwargs in ({"overlap_merge": True},
                       {"overlap_merge": True, "merge_compression": INT8},
                       {"merge_compression": INT8},
                       {"overlap_merge": True, "merge_every": 4},
                       {"merge_compression": INT8, "merge_every": 5}):
            r_scan = train_linreg(grid, X, y, lr=0.05, steps=24,
                                  **kwargs)
            r_py = train_linreg(grid, X, y, lr=0.05, steps=24,
                                engine="python", **kwargs)
            np.testing.assert_array_equal(
                np.asarray(r_scan.w), np.asarray(r_py.w)), kwargs
            assert len(r_scan.history) == len(r_py.history) == 24

    def test_overlap_converges_within_tolerance(self):
        """One round of staleness must not derail convergence at
        cadence 1 (classic pipelined SGD)."""
        X, y, _ = datasets.regression(KEY, 800, 8)
        w_star = np.asarray(closed_form(X, y))
        grid = make_cpu_grid(8)
        errs = {}
        for ovl in (False, True):
            res = train_linreg(grid, X, y, lr=0.05, steps=200,
                               overlap_merge=ovl)
            errs[ovl] = float(np.linalg.norm(np.asarray(res.w) - w_star))
        assert errs[True] <= 1.5 * errs[False] + 0.05, errs

    def test_overlap_history_and_callbacks_aligned(self):
        X, y, _ = datasets.regression(KEY, 200, 4)
        grid = make_cpu_grid(4)
        data, n, lf, uf, w0 = make_linreg_step(grid, X, y, lr=0.05)
        seen = []
        _, hist = grid.fit(init_state=w0, local_fn=lf, update_fn=uf,
                           data=data, steps=10, overlap_merge=True,
                           callback=lambda s, st, m: seen.append(s))
        assert seen == list(range(10))
        assert len(hist) == 10

    def test_overlap_cadence_first_round_metrics_match_exact(self):
        """Round 1 of the cadence-k pipeline is the same phase from the
        same state as the exact engine's round 1 — its reported metrics
        must match."""
        X, y, _ = datasets.regression(KEY, 240, 5)
        grid = make_cpu_grid(4)
        r_ovl = train_linreg(grid, X, y, lr=0.05, steps=12,
                             merge_every=4, overlap_merge=True)
        r_base = train_linreg(grid, X, y, lr=0.05, steps=12,
                              merge_every=4)
        for j in range(4):
            np.testing.assert_allclose(
                float(r_ovl.history[j]["loss"]),
                float(r_base.history[j]["loss"]), rtol=1e-6)

    def test_overlap_cadence_rounds_all_distinct(self):
        """Regression: a replacement commit decoupled the scan into two
        identical half-rate chains — every phase ran twice and history
        repeated in k-step blocks.  The delayed-delta commit keeps one
        chain: consecutive round blocks must differ and the anchor must
        advance every round."""
        X, y, _ = datasets.regression(KEY, 320, 6)
        grid = make_cpu_grid(4)
        k, rounds = 4, 6
        r = train_linreg(grid, X, y, lr=0.05, steps=k * rounds,
                         merge_every=k, overlap_merge=True)
        blocks = [tuple(float(r.history[i * k + j]["loss"])
                        for j in range(k)) for i in range(rounds)]
        for a, b in zip(blocks, blocks[1:]):
            assert a != b, blocks

    def test_overlap_cadence_keeps_full_progress_rate(self):
        """The pipelined cadence engine must track the exact engine's
        progress (staleness delays it by ~one round, it must not halve
        it: L rounds of overlap >= L-2 rounds of exact progress)."""
        X, y, _ = datasets.regression(KEY, 800, 8)
        w_star = np.asarray(closed_form(X, y))
        grid = make_cpu_grid(8)
        k, rounds = 4, 15
        err_ovl = float(np.linalg.norm(np.asarray(train_linreg(
            grid, X, y, lr=0.05, steps=k * rounds, merge_every=k,
            overlap_merge=True).w) - w_star))
        err_lag = float(np.linalg.norm(np.asarray(train_linreg(
            grid, X, y, lr=0.05, steps=k * (rounds - 2),
            merge_every=k).w) - w_star))
        assert err_ovl <= err_lag * 1.2 + 1e-4, (err_ovl, err_lag)

    def test_overlap_kmeans_converges(self):
        X, _, _ = datasets.blobs(KEY, 600, 4, k=3, spread=0.3)
        grid = make_cpu_grid(8)
        r_base = train_kmeans(grid, X, 3, iters=12)
        r_ovl = train_kmeans(grid, X, 3, iters=12, overlap_merge=True,
                             merge_compression=INT8)
        sse_base = float(r_base.history[-1]["sse"])
        sse_ovl = float(r_ovl.history[-1]["sse"])
        assert sse_ovl <= 1.2 * sse_base + 1e-3, (sse_base, sse_ovl)


class TestIntegerLeafPassthrough:
    def test_compressed_reduce_int_leaves_exact(self):
        """int32 histogram/count leaves must take the exact psum, not a
        quantizer that treats them as fp32 (regression: int32 counts
        were quantized and came back corrupted)."""
        from repro.launch.mesh import make_host_mesh
        mesh = make_host_mesh(1, 1)
        grads = {"hist": jnp.arange(300, dtype=jnp.int32),
                 "g": jnp.linspace(-3.0, 7.0, 64)}
        err = comp.init_error_state(grads)
        cfg = CompressionConfig(bits=8, slow_axis="data", fast_axes=())

        def body(g, e):
            return comp.compressed_reduce(g, e, cfg)

        specs = jax.tree.map(lambda _: P(), grads)
        out, new_err = shard_map(
            body, mesh=mesh, in_specs=(specs, specs),
            out_specs=(specs, specs), check_vma=False)(grads, err)
        # integer leaf: bit-exact, no error accumulated
        np.testing.assert_array_equal(np.asarray(out["hist"]),
                                      np.arange(300, dtype=np.int32))
        assert out["hist"].dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(new_err["hist"]), 0)
        # float leaf: quantized round-trip with residual accounted
        np.testing.assert_allclose(
            np.asarray(out["g"] + new_err["g"]),
            np.asarray(grads["g"]), atol=1e-6)

    def test_ef_compress_tree_int_passthrough(self):
        tree = {"counts": jnp.asarray([5, 0, 9], jnp.int32),
                "sums": jnp.asarray([1.5, -2.25, 0.125])}
        err = comp.init_error_state(tree)
        out, new_err = comp.ef_compress_tree(tree, err, INT8)
        np.testing.assert_array_equal(np.asarray(out["counts"]),
                                      [5, 0, 9])
        np.testing.assert_allclose(
            np.asarray(out["sums"] + new_err["sums"]),
            np.asarray(tree["sums"]), atol=1e-6)

    def test_degenerate_bits_rejected(self):
        """bits=1 would make qmax = 0 and the quantizer divide by zero
        (silent NaN state); the config rejects it at construction."""
        for bits in (0, 1, 17, -8):
            with pytest.raises(ValueError, match="bits"):
                CompressionConfig(bits=bits)
        CompressionConfig(bits=2)          # narrowest legal width

    def test_wire_bytes(self):
        tree = {"g": jnp.zeros((100,), jnp.float32),
                "hist": jnp.zeros((10,), jnp.int32)}
        assert comp.wire_bytes(tree, None) == 100 * 4 + 10 * 4
        # float leaf: 1 byte/elem + 4-byte scale; int leaf: native width
        assert comp.wire_bytes(tree, INT8) == 100 + 4 + 10 * 4


class TestMergeStateContinuation:
    def test_ef_continues_across_fit_calls(self):
        X, y, _ = datasets.regression(KEY, 320, 6)
        grid = make_cpu_grid(4)
        data, n, lf, uf, w0 = make_linreg_step(grid, X, y, lr=0.05)

        w_one, _ = grid.fit(init_state=w0, local_fn=lf, update_fn=uf,
                            data=data, steps=100,
                            merge_compression=INT8)
        holder: dict = {}
        w_half, _ = grid.fit(init_state=w0, local_fn=lf, update_fn=uf,
                             data=data, steps=50,
                             merge_compression=INT8, merge_state=holder)
        assert "error" in holder
        w_two, _ = grid.fit(init_state=w_half, local_fn=lf, update_fn=uf,
                            data=data, steps=50,
                            merge_compression=INT8, merge_state=holder)
        np.testing.assert_allclose(np.asarray(w_two), np.asarray(w_one),
                                   rtol=1e-6, atol=1e-7)

    def test_dropping_ef_between_fits_diverges(self):
        """The holder exists because the residual matters: restarting
        with a zero buffer mid-run gives a (slightly) different
        trajectory than the continuous one."""
        X, y, _ = datasets.regression(KEY, 320, 6)
        grid = make_cpu_grid(4)
        data, n, lf, uf, w0 = make_linreg_step(grid, X, y, lr=0.05)
        holder: dict = {}
        w_half, _ = grid.fit(init_state=w0, local_fn=lf, update_fn=uf,
                             data=data, steps=50,
                             merge_compression=INT8, merge_state=holder)
        w_cont, _ = grid.fit(init_state=w_half, local_fn=lf,
                             update_fn=uf, data=data, steps=50,
                             merge_compression=INT8, merge_state=holder)
        w_drop, _ = grid.fit(init_state=w_half, local_fn=lf,
                             update_fn=uf, data=data, steps=50,
                             merge_compression=INT8)
        assert not np.array_equal(np.asarray(w_cont), np.asarray(w_drop))


class TestTrainerCheckpointsEF:
    def _pieces(self, tmp_path, holder):
        def step_fn(state, batch):
            w = state["w"] - 0.1 * batch["g"]
            return {"w": w}, {"loss": jnp.sum(w ** 2)}

        cfg = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=5,
                            log_every=100, merge_compression=INT8)
        return Trainer(step_fn, {"w": jnp.ones((3,))},
                       lambda s: {"g": jnp.ones((3,))}, cfg,
                       merge_state=holder)

    def test_ef_buffer_round_trips_through_checkpoint(self, tmp_path):
        holder = {"error": {"g": jnp.asarray([0.5, -0.25, 0.0])}}
        tr = self._pieces(tmp_path, holder)
        tr.run(10)
        # resume: a fresh holder gets the checkpointed residual back
        holder2 = {"error": {"g": jnp.zeros((3,))}}
        tr2 = self._pieces(tmp_path, holder2)
        assert tr2.start_step == 10
        np.testing.assert_allclose(np.asarray(holder2["error"]["g"]),
                                   np.asarray(holder["error"]["g"]))
        np.testing.assert_allclose(np.asarray(tr2.state["w"]),
                                   np.asarray(tr.state["w"]))

    def test_compression_mismatch_refuses_resume(self, tmp_path):
        holder = {"error": {"g": jnp.zeros((3,))}}
        tr = self._pieces(tmp_path, holder)
        tr.run(10)

        def step_fn(state, batch):
            return state, {"loss": jnp.zeros(())}

        bad_cfg = TrainerConfig(
            ckpt_dir=str(tmp_path),
            merge_compression=CompressionConfig(bits=4))
        with pytest.raises(ValueError, match="compression"):
            Trainer(step_fn, {"w": jnp.ones((3,))}, lambda s: {},
                    bad_cfg, merge_state={"error": {"g": jnp.zeros((3,))}})

    def test_unseeded_holder_resume_gives_clear_error(self, tmp_path):
        """A restarting process with an empty holder meeting a
        compressed checkpoint must get told to seed the buffer, not a
        structure-mismatch crash."""
        holder = {"error": {"g": jnp.zeros((3,))}}
        tr = self._pieces(tmp_path, holder)
        tr.run(10)
        with pytest.raises(ValueError, match="init_merge_error"):
            self._pieces(tmp_path, {})

    def test_seeded_holder_resumes_bare_checkpoint(self, tmp_path):
        """Migration path: enabling compression over a run whose
        checkpoints predate it restores the model and keeps the seeded
        buffer as the fresh residual."""
        def step_fn(state, batch):
            return {"w": state["w"] - 0.1}, {"loss": jnp.zeros(())}

        bare_cfg = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=5)
        tr = Trainer(step_fn, {"w": jnp.ones((2,))}, lambda s: {},
                     bare_cfg)
        tr.run(10)
        seeded = {"error": {"g": jnp.asarray([0.5, 0.5])}}
        cfg = TrainerConfig(ckpt_dir=str(tmp_path),
                            merge_compression=INT8)
        tr2 = Trainer(step_fn, {"w": jnp.ones((2,))}, lambda s: {},
                      cfg, merge_state=seeded)
        assert tr2.start_step == 10
        np.testing.assert_allclose(np.asarray(tr2.state["w"]),
                                   np.asarray(tr.state["w"]))
        np.testing.assert_allclose(np.asarray(seeded["error"]["g"]),
                                   [0.5, 0.5])

    def test_midrun_recovery_through_bare_checkpoint(self, tmp_path):
        """Regression: fault recovery must use the same layout-robust
        restore as construction — a seeded run resumed over bare
        pre-compression checkpoints must replay through them, not crash
        on a template mismatch."""
        def ok_step(state, batch):
            return {"w": state["w"] - 0.1}, {"loss": jnp.zeros(())}

        bare_cfg = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=2,
                                 log_every=1)
        Trainer(ok_step, {"w": jnp.ones((2,))}, lambda s: {},
                bare_cfg).run(6)

        calls = {"n": 0}

        def flaky_step(state, batch):
            calls["n"] += 1
            if calls["n"] == 2:          # fail once mid-run post-resume
                raise RuntimeError("injected fault")
            return {"w": state["w"] - 0.1}, {"loss": jnp.zeros(())}

        cfg = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=2,
                            log_every=1, merge_compression=INT8)
        tr = Trainer(flaky_step, {"w": jnp.ones((2,))}, lambda s: {},
                     cfg, merge_state={"error": {"g": jnp.zeros((2,))}})
        out = tr.run(4)                  # recovers and completes
        assert out["restarts"] == 1

    def test_genuine_structure_mismatch_not_misdiagnosed(self, tmp_path):
        """Regression: a bare-vs-bare structure mismatch must surface as
        such — not as advice to seed a merge_state buffer."""
        def step_fn(state, batch):
            return state, {"loss": jnp.zeros(())}

        cfg = TrainerConfig(ckpt_dir=str(tmp_path))
        Trainer(step_fn, {"w": jnp.ones((2,))}, lambda s: {}, cfg).run(3)
        with pytest.raises(ValueError, match="structure mismatch"):
            Trainer(step_fn, {"renamed": jnp.ones((2,))}, lambda s: {},
                    cfg)

    def test_bare_state_checkpoints_unchanged(self, tmp_path):
        """No holder -> PR 2 checkpoint layout (backward compatible)."""
        def step_fn(state, batch):
            return {"w": state["w"] - 0.1}, {"loss": jnp.zeros(())}

        cfg = TrainerConfig(ckpt_dir=str(tmp_path), ckpt_every=100)
        tr = Trainer(step_fn, {"w": jnp.ones(())}, lambda s: {}, cfg)
        tr.run(3)
        tr2 = Trainer(step_fn, {"w": jnp.ones(())}, lambda s: {}, cfg)
        assert tr2.start_step == 3
        np.testing.assert_allclose(float(tr2.state["w"]),
                                   float(tr.state["w"]))


class TestMergeOverlapReport:
    def test_sync_interleaving_detected(self):
        from repro.roofline import analysis as ra
        hlo = """
HloModule m

%body (p: (f32[4], f32[4])) -> (f32[4], f32[4]) {
  %p = (f32[4]{0}, f32[4]{0}) parameter(0)
  %g0 = f32[4]{0} get-tuple-element(%p), index=0
  %g1 = f32[4]{0} get-tuple-element(%p), index=1
  %ar = f32[4]{0} all-reduce(%g1), replica_groups={{0,1}}, to_apply=%add
  %d = f32[4]{0} dot(%g0, %g0), lhs_contracting_dims={0}, rhs_contracting_dims={0}
  ROOT %t = (f32[4]{0}, f32[4]{0}) tuple(%ar, %d)
}

%cond (p: (f32[4], f32[4])) -> pred[] {
  %p = (f32[4]{0}, f32[4]{0}) parameter(0)
  %c = s32[] constant(8)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (a: f32[4]) -> (f32[4], f32[4]) {
  %a = f32[4]{0} parameter(0)
  %t0 = (f32[4]{0}, f32[4]{0}) tuple(%a, %a)
  ROOT %w = (f32[4]{0}, f32[4]{0}) while(%t0), condition=%cond, body=%body
}
"""
        rep = ra.merge_overlap_report(hlo)
        assert rep["while_bodies"] == 1
        assert rep["sync_all_reduces"] == 1
        assert rep["dots_after_sync_all_reduce"] == 1
        assert rep["overlapped"] is True

    def test_serial_schedule_not_overlapped(self):
        from repro.roofline import analysis as ra
        hlo = """
HloModule m

%body (p: (f32[4], f32[4])) -> (f32[4], f32[4]) {
  %p = (f32[4]{0}, f32[4]{0}) parameter(0)
  %g0 = f32[4]{0} get-tuple-element(%p), index=0
  %d = f32[4]{0} dot(%g0, %g0), lhs_contracting_dims={0}, rhs_contracting_dims={0}
  %ar = f32[4]{0} all-reduce(%d), replica_groups={{0,1}}, to_apply=%add
  ROOT %t = (f32[4]{0}, f32[4]{0}) tuple(%ar, %d)
}

ENTRY %main (a: f32[4]) -> (f32[4], f32[4]) {
  %a = f32[4]{0} parameter(0)
  %t0 = (f32[4]{0}, f32[4]{0}) tuple(%a, %a)
  ROOT %w = (f32[4]{0}, f32[4]{0}) while(%t0), condition=%cond, body=%body
}
"""
        rep = ra.merge_overlap_report(hlo)
        assert rep["overlapped"] is False
