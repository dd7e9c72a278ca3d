"""``step_mfu`` (the whole local step): the least time of one local
step on one chip at the published peaks (the larger of its operations
over the peak for their datatype and the bytes it must read once over
the HBM bandwidth, counted from the cell's shapes by the algorithm's
``work``), over the measured ``step_ms``, in percent."""

from bench import peaks
from bench.metrics import step_ms


def read(ctx):
    ms = step_ms.read(ctx)
    if not ms:
        return None
    return 100.0 * peaks.least_time_s(ctx.work["step"], ctx.peaks) / (ms / 1e3)
