"""``lut_activation_roofline`` (kernels: ``kernels/lut_activation``, the
LUT sigmoid of the int8 logistic regression): the least time of a
step's lookups on one chip over the device time of the
``lut_activation`` kernels per step, on the slowest chip, in percent.

The kernel is found by the name the program gives it: its trace event's
text carries ``kernel_metadata`` ``{"kernel":"lut_activation"}``.  The
least time is the step's float32 logits read and probabilities written,
8 bytes a row over the HBM bandwidth, for the rows a step uses on one
chip (as ``bench/algos/logreg.work`` counts them)."""

from bench import peaks
from bench import trace_reduce as tr
from bench.algos.logreg import rows_per_vdpu

KERNEL = '"kernel":"lut_activation"'


def kernel_ns(dev, window) -> tuple:
    """(nanoseconds, count) of the window's ``lut_activation`` kernels."""
    hits = [(s, e) for s, e, n in tr.clip(dev.ops, *window) if KERNEL in n]
    return sum(e - s for s, e in hits), len(hits)


def rows(cfg: dict, traffic: dict) -> int:
    chips = cfg["chips"]
    per = traffic.get("batch_size") or rows_per_vdpu(cfg)
    return min((cfg["n_vdpus"] // chips) * per, cfg["data"]["rows"] // chips)


def read(ctx):
    steps = ctx.out["completed"] * ctx.out["steps_per_fit"]
    ns = max((kernel_ns(d, ctx.trace.window)[0] for d in ctx.trace.devices),
             default=0)
    if not steps or not ns:
        return None
    work = {"ops": {}, "bytes": 8 * rows(ctx.cfg, ctx.traffic)}
    return 100.0 * peaks.least_time_s(work, ctx.peaks) / (ns / 1e9 / steps)
