"""Run one cell of the benchmark once and report it.

Everything that belongs to one cell is found by name from
``BENCHMARK.json``: the configuration's file (its ``file``), the traffic
mix ``bench/traffic/<traffic>.json``, the algorithm module
``bench/algos/<algo>.py`` that the configuration names (its data, the
work of a step, its plain reference and comparison), the driver
``bench/drivers/<driver>.py`` that the traffic names (set-up and the
measured window), the limits of the comparison
``bench/limits/<cell>.json`` and one reader ``bench/metrics/<name>.py``
per per-layer metric.  A new cell, configuration, traffic mix or metric
is new files and entries; no file here changes for it.
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import os
import shutil
import tempfile
import time
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
WINDOW = "bench.window"
FIT = "bench.fit"
COMPILE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "jax.trace",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration":
                      "jax.lower",
                  "/jax/core/compile/backend_compile_duration":
                      "jax.compile"}


class Refused(RuntimeError):
    """The run cannot give a result here: no accelerator, too few
    chips, an unknown chip, or a cell the files do not define."""


# -- finding a cell's files by name ------------------------------------------


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(bench: dict, name: str, root: str = ROOT) -> dict:
    """The cell's entry, its configuration, its traffic and its
    limits (``{}`` where the cell has none yet)."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    limits_path = os.path.join(root, "bench", "limits", f"{name}.json")
    return {
        "cell": cell,
        "cfg": load_json(os.path.join(root, entry["file"])),
        "traffic": load_json(os.path.join(root, "bench", "traffic",
                                          f"{cell['traffic']}.json")),
        "limits": (load_json(limits_path)["limits"]
                   if os.path.exists(limits_path) else {}),
    }


def cell_metrics(bench: dict, name: str) -> tuple:
    """(end-to-end, per-layer) metric entries the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moves = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moves)]
    return e2e, layer


def module(root: str, kind: str, name: str):
    """``bench/<kind>/<name>.py`` of the checkout at ``root``."""
    path = os.path.join(root, "bench", kind, f"{name}.py")
    if not os.path.exists(path):
        raise Refused(f"no {kind} module {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def make_estimator(cfg: dict):
    from repro.core import mlalgos

    kw = dict(cfg["estimator"])
    return getattr(mlalgos, kw.pop("class"))(**kw)


# -- the machine -------------------------------------------------------------


def place_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache, always at a fixed directory in
    the checkout (``$JAX_COMPILATION_CACHE_DIR`` is overridden), so that
    two checkouts never share compiled programs.  Every program is kept,
    however quickly it compiled, so that a second run compiles
    nothing."""
    import jax

    path = os.path.join(root, "bench", ".cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def devices_for(chips: int, require_accelerator: bool):
    """The chips the cell runs on and their published peaks."""
    import jax

    from bench import peaks

    devs = jax.devices()
    kind = devs[0].device_kind
    if require_accelerator:
        if devs[0].platform != "tpu":
            raise Refused(f"no TPU: JAX's backend is {devs[0].platform!r}")
        if len(devs) < chips:
            raise Refused(f"the cell needs {chips} chips, JAX finds "
                          f"{len(devs)}")
        try:
            return devs[:chips], peaks.peaks_for(kind)
        except peaks.UnknownDevice as e:
            raise Refused(str(e)) from None
    return devs[:chips], peaks.PEAKS["TPU v5 lite"]


def seed_key(seed: int):
    """A PRNG key from any whole number below 2**64."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def memory_peak_bytes(devices) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)


class CompileSpans:
    """Host wall-clock spans of JAX's tracing, lowering and backend
    compilation (a persistent-cache read included), from JAX's own
    monitoring events."""

    def __init__(self):
        import jax

        self.spans = []
        self._mon = jax.monitoring
        self._mon.register_event_time_span_listener(self._span)

    def _span(self, event, start, end, **kw):
        if event in COMPILE_EVENTS:
            self.spans.append((start, end, f"{COMPILE_EVENTS[event]}:"
                               f"{kw.get('fun_name', '')}"))

    def close(self):
        self._mon.unregister_event_time_span_listener(self._span)


@contextlib.contextmanager
def profiled(log_dir: str | None):
    """The profiler's trace of the block, written to ``log_dir`` (off
    where it is ``None``).  Python function tracing stays off: it would
    slow the host many times over and its events are not read."""
    if log_dir is None:
        yield
        return
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


# -- one run -------------------------------------------------------------------


def run_cell(name: str, seed: int, seconds: float, trace: bool, *,
             root: str = ROOT, bench: dict | None = None,
             require_accelerator: bool = True,
             t_start: float | None = None) -> tuple:
    """Set up, measure and check one cell; returns ``(result, checks)``:
    the result line's object and the numbers compared, each beside its
    limit."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = bench or load_json(os.path.join(root, "BENCHMARK.json"))
    files = cell_files(bench, name, root)
    cell, cfg, traffic = files["cell"], files["cfg"], files["traffic"]
    if cfg["chips"] != cell["chips"]:
        raise Refused(f"{name}: the configuration runs on {cfg['chips']} "
                      f"chips, the cell asks for {cell['chips']}")

    devices, peaks = devices_for(cell["chips"], require_accelerator)
    place_compile_cache(root)
    mod = module(root, "algos", cfg["algo"])
    drv = module(root, "drivers", traffic["driver"])
    compiles = CompileSpans()
    ctx = types.SimpleNamespace(
        name=name, seed=seed, seconds=seconds, trace=trace, cfg=cfg,
        traffic=traffic, devices=devices, peaks=peaks, algo=mod,
        compiles=compiles, t_start=t_start, make_estimator=make_estimator,
        key=seed_key(seed),
        trace_dir=tempfile.mkdtemp(prefix="bench-trace-") if trace else None)
    try:
        out = drv.run(ctx)
        ctx.memory_peak_bytes = memory_peak_bytes(devices)
        checks, failed = check(ctx, out, files["limits"])
    finally:
        compiles.close()
    e2e, layer = cell_metrics(bench, name)
    metrics = {}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": ctx.memory_peak_bytes}
    result = {"correct": None, "attempted": out["attempted"],
              "failed": failed, "metrics": metrics, "device": device}
    if trace:
        ctx.out, ctx.work = out, mod.work(cfg, traffic)
        read_trace(ctx, out, device, result)
        for m in layer:
            value = module(root, "metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in e2e:
            if m["name"] in out["end_to_end"]:
                metrics[m["name"]] = {"value": out["end_to_end"][m["name"]],
                                      "unit": m["unit"]}
    result["correct"] = bool(
        checks and failed == 0 and out["completed"] > 0
        and all(within(c["value"], c["limit"]) for c in checks.values()))
    result["checks"] = checks
    return result, checks


def check(ctx, out, limits: dict) -> tuple:
    """Compare every fit of the window with the plain reference, once
    the window has closed and the program's state is freed.  Each
    number is the worst over the fits; returns ``(checks, failed)``."""
    answers = [ctx.algo.answer(r) for r in out.pop("results")]
    out.pop("grid", None)
    gc.collect()
    ref = ctx.algo.reference(ctx.cfg, ctx.traffic, out["X"], out["y"],
                             ctx.seed)
    del out["X"], out["y"]
    worst, failed = {}, out["attempted"] - len(answers)
    for ans in answers:
        nums = ctx.algo.compare(ans, ref)
        if not all(within(v, limits.get(k)) for k, v in nums.items()):
            failed += 1
        for k, v in nums.items():
            worst[k] = max(worst.get(k, v), v)
    checks = {k: {"value": v, "limit": limits.get(k)}
              for k, v in sorted(worst.items())}
    return checks, failed


def within(value: float, limit) -> bool:
    """A number passes only below a limit that exists (a cell with no
    limits yet is never correct)."""
    return limit is not None and value <= limit


def read_trace(ctx, out, device, result):
    """Reduce the window's trace; adds ``busy_s``, ``window_s`` and the
    breakdown, and leaves the reduced trace on ``ctx`` for the
    metric readers."""
    from bench import trace_reduce as tr

    try:
        trace = tr.load(tr.find_xplane(ctx.trace_dir), window_name=WINDOW)
    finally:
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
    # the host's compile spans, moved onto the trace's clock
    offset = trace.window[0] - out["window_wall"][0] * 1e9
    trace.host = sorted(trace.host + [
        (int(s * 1e9 + offset), int(e * 1e9 + offset), n, "jax")
        for s, e, n in ctx.compiles.spans])
    ctx.trace = trace
    busy = [tr.busy_ns(d, trace.window) for d in trace.devices] or [0]
    device["busy_s"] = sum(busy) / len(busy) / 1e9
    device["window_s"] = trace.window_ns / 1e9
    if not trace.devices:       # a trace with no chip in it (the CPU)
        return
    d0 = trace.devices[0]
    gaps = tr.gaps(d0, trace.window)[:10]
    result["breakdown"] = {
        "device_ops": [[k, v / 1e9] for k, v in tr.top_ops(d0, trace.window)],
        "idle_gaps": [[tr.label(g, trace.host, ignore=(WINDOW, FIT)),
                       (g[1] - g[0]) / 1e9] for g in gaps],
    }
