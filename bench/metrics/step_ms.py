"""``step_ms`` (scan engine: ``PimGrid.make_runner``'s scan of local
step, merge and update): device milliseconds of the runner's program
(``jit_runner`` in the trace's ``XLA Modules``) per local step, on the
slowest chip."""

from bench import trace_reduce as tr

PROGRAM = "jit_runner"


def read(ctx):
    steps = ctx.out["completed"] * ctx.out["steps_per_fit"]
    ns = max((tr.module_ns(d, PROGRAM, ctx.trace.window)[0]
              for d in ctx.trace.devices), default=0)
    if not steps or not ns:
        return None
    return ns / steps / 1e6
