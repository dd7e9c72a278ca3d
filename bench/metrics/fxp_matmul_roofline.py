"""``fxp_matmul_roofline`` (kernels: ``kernels/fxp_matmul`` through
``dispatch.hybrid_matmul``): the least time of a step's int8 products
on one chip over the device time of the ``fxp_matmul`` kernels per
step, on the slowest chip, in percent.

The kernel has no name in the trace: it is the ``tpu_custom_call`` whose
operands are all ``s8`` and whose one result is ``s32``."""

from bench import peaks
from bench import trace_reduce as tr


def is_kernel(results, operands) -> bool:
    return (len(results) == 1 and results[0][0] == "s32" and bool(operands)
            and all(t == "s8" for t, _ in operands))


def read(ctx):
    steps = ctx.out["completed"] * ctx.out["steps_per_fit"]
    ns = max((tr.kernel_ns(d, ctx.trace.window, is_kernel)[0]
              for d in ctx.trace.devices), default=0)
    if not steps or not ns:
        return None
    least = peaks.least_time_s(ctx.work["fxp_matmul"], ctx.peaks)
    return 100.0 * least / (ns / 1e9 / steps)
