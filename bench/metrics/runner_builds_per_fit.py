"""``runner_builds_per_fit`` (entry layer: ``PimGrid.make_runner``'s
cache): runner-cache misses a fit makes, the program's
``pim.runner_build`` events that start in the window over the fits
completed in it.  A miss means a new jitted runner, traced on its first
call."""

from bench import spans


def read(ctx):
    fits = ctx.out["completed"]
    if not fits or not spans.instrumented(ctx.trace):
        return None
    t0, t1 = ctx.trace.window
    builds = [s for s, _, n, _ in ctx.trace.host
              if n == spans.RUNNER_BUILD and t0 <= s < t1]
    return len(builds) / fits
