"""``kmeans_assign_roofline`` (kernels: ``kernels/kmeans_assign``): the
least time of an iteration's distances and per-cluster sums on one chip
over the device time of the ``kmeans_assign`` kernel per iteration, on
the slowest chip, in percent.

The kernel has no name in the trace: it is the ``tpu_custom_call`` with
three ``f32`` operands (rows, centroids, row mask) and three ``f32``
results (sums, counts, squared distance)."""

from bench import peaks
from bench import trace_reduce as tr


def is_kernel(results, operands) -> bool:
    return (len(results) == 3 and len(operands) == 3
            and all(t == "f32" for t, _ in results + operands))


def read(ctx):
    steps = ctx.out["completed"] * ctx.out["steps_per_fit"]
    ns = max((tr.kernel_ns(d, ctx.trace.window, is_kernel)[0]
              for d in ctx.trace.devices), default=0)
    if not steps or not ns:
        return None
    least = peaks.least_time_s(ctx.work["kmeans_assign"], ctx.peaks)
    return 100.0 * least / (ns / 1e9 / steps)
