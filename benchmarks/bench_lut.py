"""Paper Table: LUT sigmoid vs exact vs Taylor — error and evaluation
cost (the DPU result, re-evaluated on this host).

CSV: name, us_per_call (1M elements), derived = max_err.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_fn, emit
from repro.core import lut
from repro.compile_cache import place_compile_cache


def run():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (1024, 1024)) * 4.0
    exact = jax.jit(jax.nn.sigmoid)
    t = lut.sigmoid_lut(1024)
    lut_f = jax.jit(lambda v: lut.lut_lookup(t, v))
    lut_i = jax.jit(lambda v: lut.lut_lookup_interp(t, v))
    taylor = jax.jit(lut.taylor_sigmoid)

    want = np.asarray(jax.nn.sigmoid(x), np.float64)

    def maxerr(fn):
        return float(np.max(np.abs(np.asarray(fn(x), np.float64) - want)))

    emit("sigmoid_exact_1M", time_fn(exact, x), "0")
    emit("sigmoid_lut_1M", time_fn(lut_f, x), f"{maxerr(lut_f):.2e}")
    emit("sigmoid_lut_interp_1M", time_fn(lut_i, x),
         f"{maxerr(lut_i):.2e}")
    emit("sigmoid_taylor_1M", time_fn(taylor, x), f"{maxerr(taylor):.2e}")


if __name__ == "__main__":
    place_compile_cache()
    run()
