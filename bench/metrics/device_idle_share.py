"""``device_idle_share`` (device): the share of the traced window in
which no operation ran on the chip, 100 x (1 - union of the ``XLA Ops``
intervals / window), averaged over the cell's chips, in percent."""

from bench import trace_reduce as tr


def read(ctx):
    w = ctx.trace.window_ns
    if not w or not ctx.trace.devices:
        return None
    busy = [tr.busy_ns(d, ctx.trace.window) for d in ctx.trace.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy) / w)
