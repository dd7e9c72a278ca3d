"""Paper Table: training-quality parity of the fixed-point / LUT variants
(the paper's central accuracy claim).

CSV: name, us(=0, not timed), derived = accuracy/SSE/error metric.
"""

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.core import datasets, make_cpu_grid
from repro.core.mlalgos import train_linreg, train_logreg, train_kmeans
from repro.core.mlalgos.linreg import closed_form
from repro.core.mlalgos.logreg import accuracy
from repro.core import lut
from repro.compile_cache import place_compile_cache


def run():
    key = jax.random.PRNGKey(0)
    grid = make_cpu_grid(64)

    X, y, _ = datasets.regression(key, 8192, 32)
    w_cf = closed_form(X, y)
    for prec in ("fp32", "int16", "int8"):
        res = train_linreg(grid, X, y, lr=0.05, steps=300, precision=prec)
        err = float(jnp.max(jnp.abs(res.w - w_cf)))
        emit(f"linreg_{prec}_maxerr_vs_exact", 0.0, f"{err:.2e}")

    Xc, yc, _ = datasets.binary_classification(key, 8192, 32)
    for prec in ("fp32", "int16", "int8"):
        for sig in ("exact", "lut"):
            r = train_logreg(grid, Xc, yc, lr=0.5, steps=200,
                             precision=prec, sigmoid=sig)
            emit(f"logreg_{prec}_{sig}_accuracy", 0.0,
                 f"{accuracy(r.w, Xc, yc):.4f}")
    r = train_logreg(grid, Xc, yc, lr=0.5, steps=200, sigmoid="taylor")
    emit("logreg_fp32_taylor_accuracy", 0.0,
         f"{accuracy(r.w, Xc, yc):.4f}")

    Xk, _, _ = datasets.blobs(key, 8192, 16, 8)
    for prec in ("fp32", "int16", "int8"):
        res = train_kmeans(grid, Xk, 8, iters=20, precision=prec)
        emit(f"kmeans_{prec}_final_sse", 0.0,
             f"{float(res.history[-1]['sse']):.1f}")

    for n in (256, 1024, 4096):
        t = lut.sigmoid_lut(n)
        emit(f"lut_sigmoid_{n}_maxerr", 0.0,
             f"{lut.lut_max_error(t, lut._np_sigmoid):.2e}")


if __name__ == "__main__":
    place_compile_cache()
    run()
