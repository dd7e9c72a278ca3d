"""Bring-up check of the PIM training path on a TPU chip.

Drives the paper's main path once, end to end, through the entry points
a user calls (``make_cpu_grid`` / ``make_mesh_grid``, ``api.fit``,
``PredictRunner`` behind a ``MicroBatchQueue``), at deployment size, on
data made on the device from a fixed seed:

* logreg: 8,388,608 rows x 32 features over 2,048 vDPUs (4,096 rows
  each), 32 steps at merge cadence 8, int8 + LUT sigmoid against fp32;
* k-means: k=8 on 8,388,608 x 16 blobs, and one local step with the
  Pallas kernel against the pure-jnp reference;
* decision tree: depth 6, 32 bins, 4 classes on 4,194,304 x 16 rows,
  and the depth-6 level histogram against the reference;
* serving: the int8 logreg state answers single-row requests.

With ``--four-chips`` it runs only the mesh phase instead: the int8
logreg fit sharded over four chips (1 GiB of fp32 rows per chip)
against the same fit on one chip.

Every phase prints its wall time, compile time and the peak device
memory so far.  Any failed check, exception or ``MergeFallbackWarning``
exits non-zero before the last line, which on success is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
No TPU, no result: the script refuses to run on another backend.

    python chip_smoke.py [--four-chips]
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import sys
import time
import warnings

import jax
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0


class SmokeFailure(RuntimeError):
    """A check of the chip run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclasses.dataclass(frozen=True)
class Sizes:
    vdpus: int = 2048                 # the paper's system: 2,524 DPUs
    logreg_rows: int = 8_388_608      # 1 GiB fp32, 256 MiB int8 resident
    logreg_features: int = 32
    logreg_steps: int = 32
    merge_every: int = 8
    kmeans_rows: int = 8_388_608
    kmeans_features: int = 16
    kmeans_k: int = 8
    kmeans_iters: int = 10
    tree_rows: int = 4_194_304
    tree_features: int = 16
    tree_depth: int = 6
    tree_bins: int = 32
    tree_classes: int = 4
    requests: int = 256
    chips: int = 4                    # --four-chips: rows are per chip


class Phases:
    """Per-phase wall time, compile time (trace + lower + backend
    compile, from JAX's own monitoring events) and peak device memory."""

    _COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration",
                       "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self._COMPILE_EVENTS:
            self.compile_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    @contextlib.contextmanager
    def phase(self, name: str):
        c0, h0 = self.compile_s, self.cache_hits
        t0 = time.perf_counter()
        yield
        wall = time.perf_counter() - t0
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        print(f"phase {name}: wall_s={wall:.3f} "
              f"compile_s={self.compile_s - c0:.3f} "
              f"persistent_cache_hits={self.cache_hits - h0} "
              f"peak_bytes_in_use={max(peaks)}", flush=True)


def require_kernels(lowered_text: str, what: str) -> int:
    """Count the Pallas kernels Mosaic will compile in a lowered
    program; none means the kernel path did not run on the chip."""
    n = lowered_text.count("tpu_custom_call")
    print(f"{what}: tpu_custom_call x{n} in the lowered HLO", flush=True)
    check(n > 0, f"{what}: no Pallas kernel in the lowered HLO")
    return n


def logreg_fit(grid, X, y, precision: str, s: Sizes, check_hlo: bool):
    from repro.core.mlalgos import LogReg

    prog = LogReg(precision=precision, sigmoid="lut").bind(grid, X, y)
    if check_hlo:
        runner = grid.make_runner(prog.local_fn, prog.update_fn,
                                  merge_every=s.merge_every)
        lowered = runner.lower(prog.state0, prog.data,
                               length=s.logreg_steps // s.merge_every)
        require_kernels(lowered.as_text(), f"logreg {precision} runner")
    res = prog.fit(steps=s.logreg_steps, merge_every=s.merge_every)
    losses = np.array([float(h["loss"]) for h in res.history])
    acc = res.eval(X, y)["accuracy"]
    print(f"logreg {precision}: loss {losses[0]:.6f} -> {losses[-1]:.6f} "
          f"accuracy={acc:.6f}", flush=True)
    check(bool(np.all(np.isfinite(losses))),
          f"logreg {precision}: non-finite loss")
    check(losses[-1] < losses[0], f"logreg {precision}: loss did not fall")
    return res.state, acc


def phase_logreg(s: Sizes):
    from repro.core import datasets, lut as lut_mod, make_cpu_grid
    from repro.core.mlalgos import LogReg
    from repro.kernels import dispatch

    X, y, _ = datasets.binary_classification(
        jax.random.PRNGKey(SEED), s.logreg_rows, s.logreg_features)
    grid = make_cpu_grid(s.vdpus)
    w8, acc8 = logreg_fit(grid, X, y, "int8", s, check_hlo=True)
    _, acc32 = logreg_fit(grid, X, y, "fp32", s, check_hlo=False)
    print(f"logreg: int8 accuracy - fp32 accuracy = {acc8 - acc32:+.6f}",
          flush=True)
    check(abs(acc8 - acc32) <= 0.02,
          "logreg: int8 accuracy is not within 2 points of fp32")

    # the LUT sigmoid kernel against the table lookup it replaces, on
    # one logit vector per vDPU
    table = lut_mod.sigmoid_lut(n_entries=LogReg.lut_entries)
    z = 4.0 * jax.random.normal(jax.random.PRNGKey(SEED + 5),
                                (s.vdpus, s.logreg_rows // s.vdpus))
    sig = jax.jit(jax.vmap(lambda v: dispatch.lut_apply(table, v)))
    require_kernels(sig.lower(z).as_text(), "LUT sigmoid")
    same = np.array_equal(np.asarray(sig(z)),
                          np.asarray(lut_mod.lut_lookup(table, z)))
    print(f"LUT sigmoid kernel equals the table lookup: {same}", flush=True)
    check(same, "logreg: LUT kernel differs from the table lookup")
    return w8


def phase_kmeans(s: Sizes):
    from repro.core import datasets, make_cpu_grid
    from repro.core.mlalgos import KMeans, api
    from repro.kernels import dispatch

    X, labels, centers = datasets.blobs(jax.random.PRNGKey(SEED + 1),
                                        s.kmeans_rows, s.kmeans_features,
                                        s.kmeans_k)
    grid = make_cpu_grid(s.vdpus)
    km = KMeans(k=s.kmeans_k)
    res = api.fit(km, grid, X, steps=s.kmeans_iters)
    sse = np.array([float(h["sse"]) for h in res.history])
    print(f"kmeans: sse {sse[0]:.6e} -> {sse[-1]:.6e}", flush=True)
    check(bool(np.all(np.isfinite(sse))) and sse[-1] <= sse[0],
          "kmeans: SSE is not finite and falling")

    # one local step, Pallas kernel vs pure-jnp reference, at the
    # generating centers: the blobs sit far apart, so no point is near
    # a tie and the assignments must agree exactly.  The two are
    # compared per vDPU, before the merge: summed over vDPUs in one
    # program, XLA folds the reference's sum into its one-hot dot, one
    # contraction over every row, and that total is printed, not held
    data, _ = grid.shard_rows(X)

    def local(c, sl):
        return km.local_step({}, c, sl)

    per_vdpu = jax.jit(jax.vmap(local, (None, 0)))
    merged = jax.jit(lambda c, d: grid.map_reduce(local, c, d))
    require_kernels(per_vdpu.lower(centers, data).as_text(),
                    "kmeans local step")
    kern = jax.device_get(per_vdpu(centers, data))
    kern_total = jax.device_get(merged(centers, data))
    with dispatch.use_kernels(False):
        per_vdpu_ref = jax.jit(jax.vmap(local, (None, 0)))
        merged_ref = jax.jit(lambda c, d: grid.map_reduce(local, c, d))
        ref = jax.device_get(per_vdpu_ref(centers, data))
        ref_total = jax.device_get(merged_ref(centers, data))

    def rel(a, b):
        """Largest error relative to the largest magnitude, per vDPU."""
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        a, b = a.reshape(a.shape[0], -1), b.reshape(b.shape[0], -1)
        return float(np.max(np.max(np.abs(a - b), axis=1)
                            / np.max(np.abs(b), axis=1)))

    # float64 sums per vDPU over the generating labels, which the
    # centers' assignment reproduces
    V, K, D = s.vdpus, s.kmeans_k, s.kmeans_features
    Xh = np.asarray(data["X"], np.float64).reshape(-1, D)
    idx = (np.arange(V)[:, None] * K
           + np.asarray(labels).reshape(V, -1)).reshape(-1)
    exact = np.stack([np.bincount(idx, Xh[:, j], V * K)
                      for j in range(D)], axis=1).reshape(V, K, D)
    sums_err = rel(kern["sums"], ref["sums"])
    sse_err = rel(kern["sse"][:, None], ref["sse"][:, None])
    total_err = rel(kern_total["sums"][None], exact.sum(0)[None])
    print(f"kmeans kernel vs reference per vDPU: counts equal="
          f"{np.array_equal(kern['counts'], ref['counts'])} "
          f"sums rel err={sums_err:.3e} sse rel err={sse_err:.3e}; "
          f"sums vs float64 per vDPU: kernel "
          f"{rel(kern['sums'], exact):.3e} reference "
          f"{rel(ref['sums'], exact):.3e}; merged sums vs float64: "
          f"kernel {total_err:.3e} reference (one fused contraction) "
          f"{rel(ref_total['sums'][None], exact.sum(0)[None]):.3e}",
          flush=True)
    check(np.array_equal(kern["counts"], ref["counts"]),
          "kmeans: kernel counts differ from the reference")
    check(sse_err <= 1e-5 and sums_err <= 1e-5,
          "kmeans: kernel sums/SSE differ from the reference")
    check(total_err <= 1e-5, "kmeans: merged kernel sums differ from float64")


def phase_tree(s: Sizes):
    from repro.core import datasets, make_cpu_grid
    from repro.core.mlalgos import DecisionTree, api
    from repro.kernels import dispatch

    X, y = datasets.mixture_classification(
        jax.random.PRNGKey(SEED + 2), s.tree_rows, s.tree_features,
        s.tree_classes)
    grid = make_cpu_grid(s.vdpus)
    tree = DecisionTree(max_depth=s.tree_depth, n_bins=s.tree_bins,
                        n_classes=s.tree_classes)
    res = api.fit(tree, grid, X, y, steps=s.tree_depth)
    acc = res.eval(X, y)["accuracy"]
    print(f"tree: levels={len(res.history)} accuracy={acc:.6f}", flush=True)
    check(acc > 1.0 / s.tree_classes, "tree: no better than chance")

    # the deepest level's histogram (2^(depth-1) nodes), kernel vs
    # reference, over the resident binned rows and seeded node ids
    data, _, _ = tree.prepare(grid, X, y)
    n_nodes = 2 ** (s.tree_depth - 1)
    data["nidx"] = jax.random.randint(jax.random.PRNGKey(SEED + 3),
                                      data["w"].shape, 0, n_nodes)
    consts = {"n_nodes": n_nodes}

    def level():
        hist = jax.jit(lambda d: grid.map_reduce(
            lambda st, sl: tree.local_step(consts, st, sl), (), d)["H"])
        if dispatch.kernels_enabled():
            require_kernels(hist.lower(data).as_text(),
                            f"tree level of {n_nodes} nodes")
        return np.asarray(jax.device_get(hist(data)))

    kern = level()
    with dispatch.use_kernels(False):
        ref = level()
    print(f"tree histogram {kern.shape}: equal={np.array_equal(kern, ref)} "
          f"total={kern.sum():.0f}", flush=True)
    check(np.array_equal(kern, ref),
          "tree: kernel histogram differs from the reference")


class _Recorder:
    """The runner as the queue's source, keeping every batch it served
    so the answers can be checked against the same rows."""

    def __init__(self, runner):
        self.runner = runner
        self.batches = []

    def predict(self, X):
        out = self.runner.predict(X)
        self.batches.append((np.array(X), np.asarray(out)))
        return out


def phase_serving(s: Sizes, w8):
    from repro.core import datasets
    from repro.core.mlalgos import LogReg
    from repro.serving import MicroBatchQueue, PredictRunner

    rows = np.asarray(datasets.binary_classification(
        jax.random.PRNGKey(SEED + 4), s.requests, s.logreg_features)[0])
    runner = PredictRunner(LogReg(precision="int8", sigmoid="lut"), w8)
    runner.warmup(s.logreg_features)
    source = _Recorder(runner)
    q = MicroBatchQueue(source, max_batch=32, max_wait_ms=2.0)
    try:
        tickets = [q.submit(r, block=True) for r in rows]
        served = [t.get(timeout=120) for t in tickets]
    finally:
        q.close()
    answers = {r.tobytes(): a for r, a in zip(rows, served)}
    for Xb, outb in source.batches:
        want = np.asarray(runner.predict(Xb))
        check(np.array_equal(outb, want),
              "serving: a batch's answers differ from runner.predict")
        for r, a in zip(Xb, want):
            check(np.array_equal(answers[r.tobytes()], a),
                  "serving: a request got another row's answer")
    counters = runner.counters()
    stats = q.stats()
    print(f"serving: requests={stats['requests']} "
          f"batches={stats['batches']} mean_batch={stats['mean_batch']:.2f} "
          f"p50_ms={stats['p50_ms']:.3f} p99_ms={stats['p99_ms']:.3f} "
          f"steady_compile_misses={counters['steady_compile_misses']}",
          flush=True)
    check(len(served) == s.requests, "serving: requests went unanswered")
    check(counters["steady_compile_misses"] == 0,
          "serving: steady-state compile misses")


def phase_four_chips(s: Sizes):
    from repro.core import datasets, make_cpu_grid, make_mesh_grid
    from jax.sharding import NamedSharding, PartitionSpec as P

    devices = jax.devices()
    check(len(devices) == s.chips,
          f"--four-chips needs {s.chips} devices, found {len(devices)}")
    grid = make_mesh_grid(s.vdpus, pods=1)
    n = s.logreg_rows * s.chips
    rows = NamedSharding(grid.mesh, P(tuple(grid.data_axes)))
    replicated = NamedSharding(grid.mesh, P())
    # generated sharded: each chip writes only its own rows
    gen = jax.jit(lambda k: datasets.binary_classification(
        k, n, s.logreg_features),
        out_shardings=(rows, rows, replicated))
    X, y, _ = gen(jax.random.PRNGKey(SEED))
    w_mesh, acc_mesh = logreg_fit(grid, X, y, "int8", s, check_hlo=True)
    per_device = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices]
    print("mesh fit peak_bytes_in_use per device: "
          + " ".join(f"{d.id}:{b}" for d, b in zip(devices, per_device)),
          flush=True)

    one = devices[0]
    X1, y1 = jax.device_put(X, one), jax.device_put(y, one)
    del X, y
    w_one, acc_one = logreg_fit(make_cpu_grid(s.vdpus), X1, y1, "int8", s,
                                check_hlo=False)
    w_mesh, w_one = np.asarray(w_mesh), np.asarray(w_one)
    err = float(np.max(np.abs(w_mesh - w_one)) / np.max(np.abs(w_one)))
    print(f"mesh vs one device: weights max rel diff={err:.3e} "
          f"accuracy {acc_mesh:.6f} vs {acc_one:.6f}", flush=True)
    check(err <= 1e-4, "mesh fit differs from the one-device fit")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh phase")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    count = len(jax.devices())
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={count}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend {dev.platform!r})",
              file=sys.stderr)
        return 1

    from repro.compile_cache import place_compile_cache
    from repro.distributed.merge_plan import MergeFallbackWarning
    from repro.kernels import dispatch, ops

    print(f"compile cache: {place_compile_cache()}", flush=True)
    warnings.simplefilter("error", MergeFallbackWarning)
    check(not ops.interpret_mode(), "Pallas kernels would run interpreted")
    check(dispatch.kernels_enabled(), "kernel dispatch is switched off")

    s = Sizes()
    phases = Phases()
    if args.four_chips:
        with phases.phase("four_chips"):
            phase_four_chips(s)
    else:
        with phases.phase("logreg"):
            w8 = phase_logreg(s)
        with phases.phase("kmeans"):
            phase_kmeans(s)
        with phases.phase("tree"):
            phase_tree(s)
        with phases.phase("serving"):
            phase_serving(s, w8)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
