"""Reduce a profiler trace of the measured window to what the per-layer
metric readers need: device operation intervals, program (module) time,
Pallas kernel time, idle gaps and what the host did in them.

The names are matched as a TPU v5e's trace prints them (``jax.profiler``
``.xplane.pb``, read with ``jax.profiler.ProfileData``):

* each chip is a plane ``/device:TPU:<i>``; its line ``XLA Ops`` holds
  one event per executed HLO operation, named by the operation's HLO
  text (``%vmap__.28 = s32[2048,3,8192]{...} custom-call(s8[3,32]...
  custom_call_target="tpu_custom_call" ...``).  A ``while`` loop is an
  event of its own that spans the operations of its body;
* its line ``XLA Modules`` holds one event per executed program, named
  ``jit_<function>(<fingerprint>)``;
* the host's threads are lines of the plane ``/host:CPU``; annotations
  made with ``jax.profiler.TraceAnnotation`` land there by name.

Host and device events share one clock (nanoseconds from the start of
the trace).  A Pallas kernel carries no name of its own in the trace
(its ``kernel_metadata`` is empty): it is a ``tpu_custom_call`` whose
operand and result types say which kernel it is (see ``custom_calls``).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
TPU_CUSTOM_CALL = 'custom_call_target="tpu_custom_call"'
_TYPE = re.compile(r"\b(pred|[suf]\d+|bf16)\[([\d,]*)\]")
_WHILE = re.compile(r"\swhile\(")


@dataclasses.dataclass
class Device:
    """One chip's events: ``ops`` and ``modules`` as ``(start_ns,
    end_ns, name)``, sorted by start."""

    index: int
    ops: list
    modules: list


@dataclasses.dataclass
class Trace:
    devices: list
    host: list                 # (start_ns, end_ns, name, thread)
    window: tuple = (0, 0)     # (start_ns, end_ns) of the measured window

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {log_dir}, found {len(paths)}")
    return paths[0]


def _events(line):
    return [(int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def load(path: str, window_name: str | None = None) -> Trace:
    """Read an ``.xplane.pb``.  With ``window_name`` the window is the
    host annotation of that name (the first), else the span of all
    device operations."""
    from jax.profiler import ProfileData

    devices, host = [], []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {ln.name: _events(ln) for ln in plane.lines}
            devices.append(Device(int(m.group(1)),
                                  sorted(lines.get("XLA Ops", [])),
                                  sorted(lines.get("XLA Modules", []))))
        elif plane.name == "/host:CPU":
            for ln in plane.lines:
                host += [(s, e, n, ln.name) for s, e, n in _events(ln)]
    devices.sort(key=lambda d: d.index)
    host.sort()
    trace = Trace(devices=devices, host=host)
    if window_name is not None:
        spans = [(s, e) for s, e, n, _ in host if n == window_name]
        if not spans:
            raise ValueError(f"no host annotation {window_name!r} in {path}")
        trace.window = spans[0]
    else:
        ops = [iv for d in devices for iv in d.ops]
        trace.window = (min(s for s, _, _ in ops), max(e for _, e, _ in ops))
    return trace


# -- intervals -------------------------------------------------------------


def clip(intervals, t0: int, t1: int) -> list:
    out = []
    for s, e, *rest in intervals:
        s, e = max(s, t0), min(e, t1)
        if e > s:
            out.append((s, e, *rest))
    return out


def merge(intervals) -> list:
    """The union of ``(start, end, ...)`` intervals as disjoint
    ``(start, end)`` pairs."""
    out = []
    for s, e, *_ in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def union_ns(intervals) -> int:
    return sum(e - s for s, e in merge(intervals))


def busy_ns(dev: Device, window) -> int:
    """Nanoseconds of the window in which some operation ran on the
    chip."""
    return union_ns(clip(dev.ops, *window))


def gaps(dev: Device, window) -> list:
    """The idle stretches of the window, ``(start, end)``, longest
    first."""
    t0, t1 = window
    out, cur = [], t0
    for s, e in merge(clip(dev.ops, t0, t1)):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if t1 > cur:
        out.append((cur, t1))
    return sorted(out, key=lambda g: g[0] - g[1])


def label(gap, host, ignore=()) -> str:
    """What the host was doing in ``gap``: the name of the host span
    that covers most of it, the shortest among equals (the innermost);
    ``"none"`` where no span overlaps it."""
    s0, e0 = gap
    best, best_key = "none", None
    for s, e, name, _ in host:
        if s >= e0:
            break
        cover = min(e, e0) - max(s, s0)
        if cover <= 0 or name in ignore:
            continue
        key = (cover, -(e - s))
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


# -- programs and kernels --------------------------------------------------


def module_ns(dev: Device, prefix: str, window) -> tuple:
    """(nanoseconds, count) of the window's executions of programs
    named ``<prefix>(...)``."""
    hits = [iv for iv in clip(dev.modules, *window)
            if iv[2].split("(", 1)[0] == prefix]
    return sum(e - s for s, e, _ in hits), len(hits)


def hlo_types(text: str):
    """``(result_types, operand_types)`` of a custom call's HLO text,
    each a list of ``(dtype, shape)``."""
    head, _, rest = text.partition(" custom-call(")
    args = rest.split("), custom_call_target=", 1)[0]

    def types(s):
        return [(t, tuple(int(x) for x in dims.split(",") if x))
                for t, dims in _TYPE.findall(s)]

    return types(head.partition(" = ")[2]), types(args)


def custom_calls(dev: Device, window) -> list:
    """The window's Pallas kernels: ``(start, end, results, operands)``
    of every ``tpu_custom_call``."""
    return [(s, e, *hlo_types(n)) for s, e, n in clip(dev.ops, *window)
            if TPU_CUSTOM_CALL in n]


def kernel_ns(dev: Device, window, match) -> tuple:
    """(nanoseconds, count) of the window's kernels for which
    ``match(results, operands)`` holds."""
    hits = [(s, e) for s, e, r, o in custom_calls(dev, window) if match(r, o)]
    return sum(e - s for s, e in hits), len(hits)


_COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)(-start|-done)?(\.\d+)?$")


def is_collective(text: str) -> bool:
    """An operation that exchanges data between chips, by its HLO
    instruction name (``%all-reduce.3``, ``%all-reduce-start.1``,
    ``%all-reduce-done.1``, ...)."""
    return bool(_COLLECTIVE.match(text.split(" = ", 1)[0].strip()))


def exposed_collective_ns(dev: Device, window) -> tuple:
    """(nanoseconds, count) of the window's collectives: the union of
    their intervals less the time in which some other operation ran
    (loops left out, since they span their body)."""
    ops = clip(dev.ops, *window)
    coll = [iv for iv in ops if is_collective(iv[2])]
    other = merge(iv for iv in ops
                  if not is_collective(iv[2]) and not _WHILE.search(iv[2]))
    exposed = 0
    for s, e in merge(coll):
        exposed += (e - s) - union_ns(clip(other, s, e))
    return exposed, len(coll)


def top_ops(dev: Device, window, n: int = 10) -> list:
    """The operations that took most device time in the window, summed
    by the operation's name without its number (``%fusion.47`` and
    ``%fusion.48`` add up), loops left out (their body's operations are
    counted)."""
    tot = {}
    for s, e, name in clip(dev.ops, *window):
        if _WHILE.search(name):
            continue
        key = re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))
        if TPU_CUSTOM_CALL in name:
            key = f"tpu_custom_call:{key}"
        tot[key] = tot.get(key, 0) + (e - s)
    return sorted(tot.items(), key=lambda kv: -kv[1])[:n]
