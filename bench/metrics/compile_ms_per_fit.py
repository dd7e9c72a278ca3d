"""``compile_ms_per_fit`` (entry layer: ``api.fit`` -> ``Workload.bind``,
``PimGrid.make_runner``'s cache): host milliseconds a fit spends in
JAX's tracing, lowering and backend compilation, persistent-cache reads
included, in the window.  From JAX's own monitoring spans; their union,
so that a trace nested in another counts once."""

from bench import trace_reduce as tr


def read(ctx):
    fits = ctx.out["completed"]
    if not fits:
        return None
    w0, w1 = ctx.out["window_wall"]
    spans = tr.clip([(s, e) for s, e, _ in ctx.compiles.spans], w0, w1)
    return 1e3 * tr.union_ns(spans) / fits
