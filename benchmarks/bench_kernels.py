"""Kernel-path microbenchmarks.

On this CPU container the Pallas kernels execute in interpret mode
(Python per grid step — correctness harness, not a perf number), so the
timed path is the jnp reference each kernel must beat on TPU; kernel
outputs are asserted allclose against the same reference here.

CSV: name, us_per_call, derived = shape | allclose.
"""

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import time_fn, emit
from repro.kernels import dispatch, ops, ref
from repro.core import lut as lutm
from repro.core import quantize as qz
from repro.compile_cache import place_compile_cache


def run():
    key = jax.random.PRNGKey(0)

    # flash attention ref timing + kernel check
    B, H, Kh, S, D = 1, 4, 2, 512, 64
    q = jax.random.normal(key, (B, H, S, D))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, Kh, S, D))
    v = jax.random.normal(jax.random.fold_in(key, 2), (B, Kh, S, D))
    fa_ref = jax.jit(lambda a, b, c: ref.flash_attention_ref(a, b, c))
    us = time_fn(fa_ref, q, k, v)
    out = ops.flash_attention(q, k, v, block_q=128, block_k=128)
    ok = np.allclose(np.asarray(out), np.asarray(fa_ref(q, k, v)),
                     atol=2e-5)
    emit("flash_attention_ref_512", us, f"kernel_allclose={ok}")

    # LUT activation
    t = lutm.sigmoid_lut(1024)
    x = jax.random.normal(key, (512, 1024)) * 4
    lut_ref = jax.jit(
        lambda a: ref.lut_activation_ref(a, t.table, t.x_min, t.x_max))
    us = time_fn(lut_ref, x)
    out = ops.lut_activation(x, t.table, x_min=t.x_min, x_max=t.x_max)
    ok = np.array_equal(np.asarray(out), np.asarray(lut_ref(x)))
    emit("lut_activation_ref_512x1024", us, f"kernel_exact={ok}")

    # fxp matmul
    a = jax.random.randint(key, (256, 512), -128, 128, jnp.int8)
    b = jax.random.randint(key, (512, 256), -128, 128, jnp.int8)
    fxp_ref = jax.jit(ref.fxp_matmul_ref)
    us = time_fn(fxp_ref, a, b)
    ok = np.array_equal(np.asarray(ops.fxp_matmul(a, b)),
                        np.asarray(fxp_ref(a, b)))
    emit("fxp_matmul_ref_256x512x256", us, f"kernel_exact={ok}")

    # fxp matmul, non-block-aligned (exercises the pad-and-slice path);
    # the timed call is the kernel itself (interpret-mode off TPU)
    ao = jax.random.randint(key, (300, 130), -128, 128, jnp.int8)
    bo = jax.random.randint(key, (130, 70), -128, 128, jnp.int8)
    ok = np.array_equal(np.asarray(ops.fxp_matmul(ao, bo)),
                        np.asarray(fxp_ref(ao, bo)))
    emit("fxp_matmul_padded_300x130x70", time_fn(ops.fxp_matmul, ao, bo),
         f"kernel_exact={ok}")

    # hybrid int16 matmul: dispatch (Pallas limbs) vs quantize.hybrid_dot
    ah = jax.random.randint(key, (2048, 64), -32768, 32767
                            ).astype(jnp.int16)
    bh = jax.random.randint(key, (64, 1), -32768, 32767).astype(jnp.int16)
    hd_ref = jax.jit(qz.hybrid_dot)
    us = time_fn(hd_ref, ah, bh)
    disp = jax.jit(dispatch.hybrid_matmul)
    ok = np.array_equal(np.asarray(disp(ah, bh)),
                        np.asarray(hd_ref(ah, bh)))
    emit("hybrid_dot_ref_2048x64", us, f"dispatch_exact={ok}")
    emit("hybrid_matmul_dispatch_2048x64", time_fn(disp, ah, bh), "")

    # kmeans assign
    x = jax.random.normal(key, (8192, 32))
    c = jax.random.normal(jax.random.fold_in(key, 3), (16, 32))
    km_ref = jax.jit(ref.kmeans_assign_ref)
    us = time_fn(km_ref, x, c)
    s1, c1, e1 = ops.kmeans_assign(x, c)
    s2, c2, e2 = km_ref(x, c)
    ok = np.allclose(np.asarray(s1), np.asarray(s2), atol=1e-2)
    emit("kmeans_assign_ref_8192x32x16", us, f"kernel_allclose={ok}")

    # split hist
    N, F = 4096, 16
    node = jax.random.randint(key, (N,), 0, 8)
    xb = jax.random.randint(jax.random.fold_in(key, 4), (N, F), 0, 32)
    y = jax.random.randint(jax.random.fold_in(key, 5), (N,), 0, 4)
    hist_ref = jax.jit(lambda n, x_, y_: ref.split_hist_ref(
        n, x_, y_, 8, 32, 4))
    us = time_fn(hist_ref, node, xb, y)
    h1 = ops.split_hist(node, xb, y, n_nodes=8, n_bins=32, n_classes=4)
    ok = np.array_equal(np.asarray(h1), np.asarray(hist_ref(node, xb, y)))
    emit("split_hist_ref_4096x16", us, f"kernel_exact={ok}")


if __name__ == "__main__":
    place_compile_cache()
    run()
