"""Record the small chip trace that the span-metric tests read.

    python3 bench/tests/record_spans_fixture.py

on one TPU, from the root of a checkout: the fits of
``record_fixture.py`` (one int8 logreg fit, LUT sigmoid, 3 steps, and
one k-means fit, 2 iterations, over 8 vDPUs of 512 rows) by a program
that leaves its own spans (``pim.*``) and names its kernels, traced
inside a ``bench.window`` annotation, written to
``bench/tests/fixtures/spans.xplane.pb``.
"""

import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    import jax

    from bench.datasets import binary_classification, blobs
    from repro.core import make_cpu_grid
    from repro.core.mlalgos import KMeans, LogReg, api

    if jax.devices()[0].platform != "tpu":
        print("record_spans_fixture: no TPU", file=sys.stderr)
        return 2
    grid = make_cpu_grid(8)
    X, y, _ = binary_classification(jax.random.PRNGKey(0), 4096, 32)
    Xk, _, _ = blobs(jax.random.PRNGKey(1), 4096, 16, 8)
    lr = LogReg(precision="int8", sigmoid="lut")
    km = KMeans(k=8)

    def fits():
        jax.block_until_ready(api.fit(lr, grid, X, y, steps=3).state)
        jax.block_until_ready(api.fit(km, grid, Xk, steps=2).state)

    fits()                                # compile outside the trace
    log_dir = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        fits()
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    dest = os.path.join(ROOT, "bench", "tests", "fixtures", "spans.xplane.pb")
    shutil.copy(path, dest)
    shutil.rmtree(log_dir)
    print(f"wrote {dest} ({os.path.getsize(dest)} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
