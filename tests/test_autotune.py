"""Kernel block-shape autotuning (``kernels.autotune``): heuristics,
shape bucketing, the on-disk measured cache, and dispatch integration."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune as at
from repro.kernels import dispatch
from repro.kernels import fxp_matmul as _fxp
from repro.kernels import kmeans_assign as _km


@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    path = str(tmp_path / "autotune.json")
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", path)
    at.reset_cache_for_tests()
    yield path
    at.reset_cache_for_tests()


class TestNothingLowers:
    def test_autotune_raises_instead_of_storing_a_guess(self, tmp_cache,
                                                        monkeypatch):
        def refuse(*a, **k):
            raise RuntimeError("Mosaic refused the block")

        from repro.tuning import autotune as tuning_autotune
        monkeypatch.setattr(tuning_autotune, "_time_call", refuse)
        with pytest.raises(RuntimeError, match="no fxp_matmul block"):
            at.autotune("fxp_matmul", (64, 128, 32))
        assert not os.path.exists(tmp_cache)


class TestBuckets:
    def test_shape_bucket_pow2(self):
        assert at.shape_bucket((300, 130, 70)) == (512, 256, 128)
        assert at.shape_bucket((1, 128)) == (1, 128)

    def test_nearby_shapes_share_keys(self):
        k1 = at.table_key("fxp_matmul", jnp.int8, (300, 130, 70), "cpu")
        k2 = at.table_key("fxp_matmul", jnp.int8, (400, 200, 100), "cpu")
        assert k1 == k2

    def test_backend_in_key(self):
        k_cpu = at.table_key("fxp_matmul", jnp.int8, (64, 64, 64), "cpu")
        k_tpu = at.table_key("fxp_matmul", jnp.int8, (64, 64, 64), "tpu")
        assert k_cpu != k_tpu


class TestHeuristics:
    def test_interpret_prefers_single_block(self):
        """interpret mode runs the kernel body per grid step in Python —
        the heuristic collapses small problems into one block."""
        b = at.block_shapes("fxp_matmul", jnp.int8, (64, 128, 32),
                            backend="cpu")
        assert b == {"block_m": 64, "block_n": 32, "block_k": 128}
        b = at.block_shapes("kmeans_assign", jnp.float32, (5000, 16, 8),
                            backend="cpu")
        assert b == {"block_n": 5000}

    def test_interpret_chunks_oversized_k(self):
        b = at.block_shapes("fxp_matmul", jnp.int8,
                            (4096, 1 << 20, 4096), backend="cpu")
        assert b["block_m"] == 4096 and b["block_n"] == 4096
        assert b["block_k"] < 1 << 20

    def test_tpu_blocks_aligned_and_capped(self):
        b = at.block_shapes("fxp_matmul", jnp.int8, (1000, 4096, 2000),
                            backend="tpu")
        # minor dims multiples of 128, int8 sublane 32, legacy caps hold
        assert b["block_n"] % 128 == 0 and b["block_k"] % 128 == 0
        assert b["block_m"] % 32 == 0
        assert b["block_m"] <= 256 and b["block_k"] <= 512

    def test_blocks_never_exceed_shape(self):
        for backend in ("cpu", "tpu"):
            b = at.block_shapes("fxp_matmul", jnp.int8, (3, 5, 2),
                                backend=backend)
            assert b["block_m"] <= 3 and b["block_k"] <= 5 \
                and b["block_n"] <= 2

    def test_split_hist_onehot_budget(self):
        """the kernel materializes a (bn, F, nodes*bins*classes) one-hot
        per step — bn must shrink as that product grows."""
        small = at.block_shapes("split_hist", jnp.float32,
                                (1 << 16, 16, 64), backend="cpu")
        big = at.block_shapes("split_hist", jnp.float32,
                              (1 << 16, 16, 1 << 14), backend="cpu")
        assert big["block_n"] < small["block_n"]


class TestMeasuredCache:
    def test_autotune_persists_and_wins(self, tmp_cache):
        best = at.autotune("fxp_matmul", (64, 128, 32))
        # on disk
        with open(tmp_cache) as f:
            data = json.load(f)
        assert len(data["entries"]) == 1
        (key, entry), = data["entries"].items()
        assert entry["blocks"] == best
        # a fresh in-memory cache reads the measured entry back
        at.reset_cache_for_tests()
        assert at.block_shapes("fxp_matmul", jnp.int8,
                               (64, 128, 32)) == best

    def test_measured_entry_clamped_to_smaller_call(self, tmp_cache):
        at._store(at.table_key("fxp_matmul", jnp.int8, (60, 120, 30)),
                  {"block_m": 64, "block_n": 32, "block_k": 128}, 1.0)
        b = at.block_shapes("fxp_matmul", jnp.int8, (40, 100, 20))
        assert b["block_m"] <= 40 and b["block_k"] <= 100 \
            and b["block_n"] <= 20

    def test_missing_cache_file_falls_back(self, tmp_cache):
        # no file written yet -> heuristic, no crash
        b = at.block_shapes("kmeans_assign", jnp.float32, (100, 4, 3))
        assert b["block_n"] == 100

    def test_corrupt_cache_ignored(self, tmp_cache):
        with open(tmp_cache, "w") as f:
            f.write("{not json")
        at.reset_cache_for_tests()
        b = at.block_shapes("fxp_matmul", jnp.int8, (8, 8, 8))
        assert b["block_m"] == 8

    def test_autotune_kmeans_smoke(self, tmp_cache):
        best = at.autotune("kmeans_assign", (256, 8, 4))
        assert 1 <= best["block_n"] <= 256

    def test_fresh_process_store_merges_disk_entries(self, tmp_cache):
        """Regression: a process whose first cache touch is autotune()
        (in-memory cache never loaded) must merge with the on-disk
        entries, not overwrite them."""
        at._store("other|int8|64x64x64|cpu",
                  {"block_m": 8, "block_n": 8, "block_k": 8}, 1.0)
        at.reset_cache_for_tests()       # simulate a fresh process
        at.autotune("kmeans_assign", (64, 4, 2))
        with open(tmp_cache) as f:
            entries = json.load(f)["entries"]
        assert "other|int8|64x64x64|cpu" in entries
        assert any(k.startswith("kmeans_assign|") for k in entries)


_RACE_WORKER = r"""
import json, os, sys
from repro.tuning import autotune as at

wid, iters = sys.argv[1], int(sys.argv[2])
for i in range(iters):
    # fresh-process view each write: reload from disk so entries merge
    at.reset_cache_for_tests()
    at._store(f"race{wid}k{i}|int8|64x64x64|cpu",
              {"block_m": 8, "block_n": 8, "block_k": 8}, float(i))
# whatever state the race left behind must parse as a complete doc
# (the other process may have won any individual entry)
with open(at.cache_path()) as f:
    assert isinstance(json.load(f)["entries"], dict)
print("ok")
"""


class TestConcurrentWriters:
    """Two processes racing ``$REPRO_AUTOTUNE_CACHE`` must never leave
    torn/invalid JSON on disk (per-writer temp file + atomic
    ``os.replace``).  Last writer may win an *entry*, but every observable
    file state is a complete document."""

    def _spawn(self, tmp_cache, wid, iters):
        env = dict(os.environ,
                   REPRO_AUTOTUNE_CACHE=tmp_cache,
                   PYTHONPATH=os.path.join(os.path.dirname(__file__),
                                           os.pardir, "src"),
                   JAX_PLATFORMS="cpu")
        return subprocess.Popen(
            [sys.executable, "-c", _RACE_WORKER, wid, str(iters)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True)

    def test_racing_writers_never_corrupt_json(self, tmp_cache):
        procs = [self._spawn(tmp_cache, "A", 30),
                 self._spawn(tmp_cache, "B", 30)]
        reads = 0
        # poll the file while the race runs: every observable state must
        # parse (os.replace makes each publish atomic)
        while any(p.poll() is None for p in procs):
            try:
                with open(tmp_cache) as f:
                    data = json.load(f)
                assert isinstance(data.get("entries"), dict)
                reads += 1
            except FileNotFoundError:
                pass
            time.sleep(0.01)
        for p in procs:
            out, err = p.communicate(timeout=60)
            assert p.returncode == 0, f"worker failed:\n{err}"
            assert "ok" in out
        with open(tmp_cache) as f:
            entries = json.load(f)["entries"]
        # both writers reload-before-store, so entries from both sides
        # accumulate (an interleaved write may drop a handful, but the
        # survivor set is well-formed and from the expected key space)
        assert entries, "race left an empty cache"
        for key, entry in entries.items():
            assert key.startswith("race"), key
            assert set(entry["blocks"]) == {"block_m", "block_n",
                                            "block_k"}
        assert any(k.startswith("raceA") for k in entries) \
            or any(k.startswith("raceB") for k in entries)
        # no temp droppings left behind
        leftovers = [f for f in os.listdir(os.path.dirname(tmp_cache))
                     if f.endswith(".tmp")]
        assert leftovers == []

    def test_sequential_processes_merge_entries(self, tmp_cache):
        """The cross-process flavor of fresh-process merging: writer B
        starting after writer A finished must keep A's entries."""
        a = self._spawn(tmp_cache, "A", 2)
        assert a.wait(timeout=120) == 0, a.communicate()[1]
        b = self._spawn(tmp_cache, "B", 2)
        assert b.wait(timeout=120) == 0, b.communicate()[1]
        with open(tmp_cache) as f:
            entries = json.load(f)["entries"]
        assert "raceA" + "k1|int8|64x64x64|cpu" in entries
        assert "raceB" + "k1|int8|64x64x64|cpu" in entries


class TestResetIsolation:
    """``reset_cache_for_tests`` audit: what the in-memory cache keys on
    and what actually needs a reset."""

    def test_env_repoint_is_keyed_without_reset(self, tmp_cache,
                                                tmp_path, monkeypatch):
        """The in-memory cache is keyed on the resolved path, so merely
        repointing $REPRO_AUTOTUNE_CACHE is honored without a reset —
        a test that forgets the fixture cannot serve another path's
        entries."""
        at._store(at.table_key("fxp_matmul", jnp.int8, (60, 120, 30),
                               "cpu"),
                  {"block_m": 2, "block_n": 2, "block_k": 2}, 1.0)
        other = str(tmp_path / "other_cache.json")
        monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", other)
        b = at.block_shapes("fxp_matmul", jnp.int8, (60, 120, 30),
                            backend="cpu")
        assert b != {"block_m": 2, "block_n": 2, "block_k": 2}

    def test_same_path_mutation_needs_reset(self, tmp_cache):
        """Mutating the file *under the same path* is what the reset is
        for: the loaded dict is cached until reset drops it."""
        key = at.table_key("fxp_matmul", jnp.int8, (60, 120, 30), "cpu")
        at.block_shapes("fxp_matmul", jnp.int8, (60, 120, 30),
                        backend="cpu")          # loads (empty) cache
        with open(tmp_cache, "w") as f:
            json.dump({"version": 1, "entries": {key: {
                "blocks": {"block_m": 4, "block_n": 4, "block_k": 4},
                "us": 1.0}}}, f)
        stale = at.block_shapes("fxp_matmul", jnp.int8, (60, 120, 30),
                                backend="cpu")
        assert stale["block_m"] != 4            # still the loaded view
        at.reset_cache_for_tests()
        fresh = at.block_shapes("fxp_matmul", jnp.int8, (60, 120, 30),
                                backend="cpu")
        assert fresh == {"block_m": 4, "block_n": 4, "block_k": 4}

    def test_store_after_reset_does_not_resurrect_memory(self, tmp_cache):
        """After a reset, _store must rebuild its view from disk — the
        dropped in-memory entries must not leak back in."""
        at._store("ghost|int8|8x8x8|cpu",
                  {"block_m": 1, "block_n": 1, "block_k": 1}, 1.0)
        os.remove(tmp_cache)                    # disk truth: nothing
        at.reset_cache_for_tests()
        at._store("real|int8|8x8x8|cpu",
                  {"block_m": 2, "block_n": 2, "block_k": 2}, 1.0)
        with open(tmp_cache) as f:
            entries = json.load(f)["entries"]
        assert set(entries) == {"real|int8|8x8x8|cpu"}


class TestKernelsUnderTunedBlocks:
    """Any block shape the table hands out must stay numerically exact —
    padding/tiling is an implementation detail."""

    def test_fxp_matmul_odd_shapes(self):
        rng = np.random.default_rng(0)
        a = jnp.asarray(rng.integers(-100, 100, (33, 57)), jnp.int8)
        b = jnp.asarray(rng.integers(-100, 100, (57, 19)), jnp.int8)
        want = np.asarray(a, np.int32) @ np.asarray(b, np.int32)
        for blocks in ({"block_m": 33, "block_n": 19, "block_k": 57},
                       {"block_m": 8, "block_n": 8, "block_k": 16},
                       {"block_m": 16, "block_n": 4, "block_k": 57}):
            out = _fxp.fxp_matmul(a, b, interpret=True, **blocks)
            np.testing.assert_array_equal(np.asarray(out), want)

    def test_kmeans_assign_block_sweep(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(100, 5)), jnp.float32)
        c = jnp.asarray(rng.normal(size=(4, 5)), jnp.float32)
        w = jnp.ones((100,), jnp.float32)
        ref = _km.kmeans_assign(x, c, w, interpret=True, block_n=100)
        for bn in (7, 32, 64):
            out = _km.kmeans_assign(x, c, w, interpret=True, block_n=bn)
            for r, o in zip(ref, out):
                np.testing.assert_allclose(np.asarray(o), np.asarray(r),
                                           rtol=1e-5, atol=1e-5)

    def test_dispatch_parity_with_table(self, tmp_cache):
        """hybrid_matmul through the (heuristic) table still matches the
        pure-jnp reference bit-for-bit."""
        rng = np.random.default_rng(2)
        a = jnp.asarray(rng.integers(-30000, 30000, (47, 83)), jnp.int16)
        b = jnp.asarray(rng.integers(-30000, 30000, (83, 11)), jnp.int16)
        out = dispatch.hybrid_matmul(a, b)
        with dispatch.use_kernels(False):
            ref = dispatch.hybrid_matmul(a, b)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
