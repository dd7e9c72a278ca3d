"""``dispatch_idle_ms_per_fit`` (scan engine: ``PimGrid.fit``'s runner
calls): milliseconds a fit in which the chip ran nothing while the host
was inside the program's ``pim.dispatch`` spans (the jit cache lookup,
the runner's re-trace and compile-cache read where they happen, the
enqueue of each chunk), mean over the cell's chips."""

from bench import spans


def read(ctx):
    return spans.idle_ms_per_fit(ctx, spans.DISPATCH)
