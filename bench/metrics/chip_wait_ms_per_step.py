"""``chip_wait_ms_per_step`` (merge: ``PimGrid.map_reduce``'s psums
between chips): device milliseconds a local step by which the chip that
sits longest in the runner's collectives exceeds the chip that sits
least in them.  An all-reduce ends on every chip at once, so a chip that
reaches it early waits there for the last: this is that wait, the
exposed collective time beyond what the exchange takes on the chip that
arrives last.  It holds both a chip that is slower every step and the
chips' runner calls starting apart (launch skew, paid at the first
all-reduce of a call).  ``collective_ms_per_step`` less this number is
about the exchange itself.  Only collectives inside the runner's program
(``jit_runner``) are counted, so ``prepare``'s once-a-fit scale maximum
is left out.  Nothing to read on one chip."""

from bench import trace_reduce as tr

PROGRAM = "jit_runner"


def runner_collective_ns(dev, window) -> tuple:
    """(nanoseconds, count) of exposed collective time inside the
    window's runner executions on one chip."""
    ns = count = 0
    for s, e, name in tr.clip(dev.modules, *window):
        if name.split("(", 1)[0] == PROGRAM:
            n, c = tr.exposed_collective_ns(dev, (s, e))
            ns, count = ns + n, count + c
    return ns, count


def read(ctx):
    steps = ctx.out["completed"] * ctx.out["steps_per_fit"]
    per_chip = [runner_collective_ns(d, ctx.trace.window)
                for d in ctx.trace.devices]
    if not steps or len(per_chip) < 2 or not any(c for _, c in per_chip):
        return None
    ns = [n for n, _ in per_chip]
    return (max(ns) - min(ns)) / steps / 1e6
