"""The program's own host spans in a reduced trace (``trace_reduce``).

Under the profiler ``api.fit`` leaves ``pim.fit`` around each call and,
inside it, ``pim.prepare`` around the workload's ``prepare``, and from
the scan engine ``pim.dispatch`` around each chunk's runner call and
``pim.history`` around its per-step unpacking; ``PimGrid.make_runner``
leaves one ``pim.runner_build`` event a runner-cache miss
(``src/repro/core/mlalgos/api.py``, ``src/repro/core/pim.py``).  They
land on the host's threads, on the device trace's clock.

A program without them leaves no ``pim.fit`` in the window; the readers
built on this module then give nothing.
"""

from __future__ import annotations

from bench import trace_reduce as tr

FIT = "pim.fit"
PREPARE = "pim.prepare"
DISPATCH = "pim.dispatch"
HISTORY = "pim.history"
RUNNER_BUILD = "pim.runner_build"
PARTS = (PREPARE, DISPATCH, HISTORY)


def spans(trace, name: str) -> list:
    """``(start, end)`` of the host events named ``name``, clipped to
    the window."""
    return tr.clip([(s, e) for s, e, n, _ in trace.host if n == name],
                   *trace.window)


def instrumented(trace) -> bool:
    """Whether the window holds a fit that the program annotated."""
    return bool(spans(trace, FIT))


def ms_per_fit(ctx, name: str):
    """Host milliseconds a fit spends inside spans named ``name``: their
    union over the window, over the fits completed in it."""
    fits = ctx.out["completed"]
    if not fits or not instrumented(ctx.trace):
        return None
    return tr.union_ns(spans(ctx.trace, name)) / fits / 1e6


def intersect(a, b) -> list:
    """The intersection of two unions of intervals, as disjoint sorted
    ``(start, end)`` pairs."""
    a, b = tr.merge(a), tr.merge(b)
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_ns(dev, window, inside, outside=()) -> int:
    """Nanoseconds of the window in which the chip ran nothing, the host
    was inside some interval of ``inside`` and inside none of
    ``outside``."""
    idle = intersect(tr.gaps(dev, window), inside)
    return tr.union_ns(idle) - tr.union_ns(intersect(idle, outside))


def idle_split(trace) -> dict:
    """Nanoseconds of the window's device idle, mean over the chips,
    split by where the host was: inside each named part of a fit
    (``PARTS``), inside a fit but none of them (``"unattributed"``), and
    outside every fit (``"outside"``).  The named parts never overlap,
    so the five add up to the window's idle."""
    fits = spans(trace, FIT)
    named = {n: spans(trace, n) for n in PARTS}
    everything = [iv for ivs in named.values() for iv in ivs]
    split = {n: [idle_ns(d, trace.window, ivs) for d in trace.devices]
             for n, ivs in named.items()}
    split["unattributed"] = [idle_ns(d, trace.window, fits, everything)
                             for d in trace.devices]
    split["outside"] = [idle_ns(d, trace.window, [trace.window], fits)
                        for d in trace.devices]
    return {k: sum(v) / len(v) for k, v in split.items()}


def idle_ms_per_fit(ctx, name: str):
    """Milliseconds a fit in which the chip ran nothing while the host
    was inside spans named ``name``, mean over the chips: what those
    spans cost the fit, where their own length counts host time spent
    waiting for the chip as well."""
    t, fits = ctx.trace, ctx.out["completed"]
    if not fits or not t.devices or not instrumented(t):
        return None
    return idle_split(t)[name] / fits / 1e6
