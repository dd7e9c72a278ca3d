"""``collective_ms_per_step`` (merge: ``PimGrid.map_reduce``'s psums
between chips): device milliseconds a local step spends in collectives
(``all-reduce`` and its kin, by HLO name) while no other operation runs
on the chip, on the chip where that time is longest.  Nothing to read
on one chip, where the merge has no collective."""

from bench import trace_reduce as tr


def read(ctx):
    steps = ctx.out["completed"] * ctx.out["steps_per_fit"]
    per_chip = [tr.exposed_collective_ns(d, ctx.trace.window)
                for d in ctx.trace.devices]
    if not steps or not any(count for _, count in per_chip):
        return None
    return max(ns for ns, _ in per_chip) / steps / 1e6
