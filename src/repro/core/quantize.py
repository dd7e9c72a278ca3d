"""Fixed-point / quantized arithmetic — the paper's insight I1.

UPMEM DPUs have no FPU and only a native 8x8->16-bit multiplier, so the
paper trains with fixed-point (Q-format) operands and *hybrid precision*:
narrow multiplies, wide (32/64-bit) accumulation, with negligible accuracy
loss.  On TPU the same structure is profitable for a different reason —
int8 operands halve/quarter HBM and interconnect bytes and feed the MXU's
native s8xs8->s32 path — so we keep the paper's scheme and reuse it for
gradient compression (distributed/compression.py).

Two families are provided:

* ``QFormat`` — classic Qm.n fixed point (the paper's representation):
  value = int / 2**frac_bits, saturating casts, exact bit behaviour.
* dynamic symmetric quantization (per-tensor / per-row scales) — the
  "quantization" variant the paper cites [178, 179], used for dataset
  storage and gradient compression.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as _np

# ---------------------------------------------------------------------------
# Q-format fixed point
# ---------------------------------------------------------------------------

_INT_DTYPES = {8: jnp.int8, 16: jnp.int16, 32: jnp.int32, 64: jnp.int64}


@dataclasses.dataclass(frozen=True)
class QFormat:
    """Qm.n fixed-point format stored in a ``total_bits`` signed integer.

    ``value = stored_int * 2**-frac_bits``.  ``int_bits`` excludes the sign
    bit, so ``total_bits = 1 + int_bits + frac_bits`` must hold.
    """

    int_bits: int
    frac_bits: int

    def __post_init__(self):
        if self.total_bits not in _INT_DTYPES:
            raise ValueError(f"unsupported total bits {self.total_bits}")

    @property
    def total_bits(self) -> int:
        return 1 + self.int_bits + self.frac_bits

    @property
    def dtype(self):
        return _INT_DTYPES[self.total_bits]

    @property
    def scale(self) -> float:
        return float(2 ** self.frac_bits)

    @property
    def max_value(self) -> float:
        return (2 ** (self.total_bits - 1) - 1) / self.scale

    @property
    def min_value(self) -> float:
        return -(2 ** (self.total_bits - 1)) / self.scale

    # -- conversions --------------------------------------------------------

    def quantize(self, x: jax.Array, stochastic: bool = False,
                 key: jax.Array | None = None) -> jax.Array:
        """Float -> Qm.n integer, saturating.  Optional stochastic rounding
        (paper-adjacent: unbiased rounding keeps GD updates unbiased)."""
        scaled = jnp.asarray(x, jnp.float32) * self.scale
        if stochastic:
            if key is None:
                raise ValueError("stochastic rounding requires a PRNG key")
            noise = jax.random.uniform(key, scaled.shape, jnp.float32)
            q = jnp.floor(scaled + noise)
        else:
            q = jnp.round(scaled)
        lo = -(2 ** (self.total_bits - 1))
        hi = 2 ** (self.total_bits - 1) - 1
        return jnp.clip(q, lo, hi).astype(self.dtype)

    def dequantize(self, q: jax.Array, dtype=jnp.float32) -> jax.Array:
        return q.astype(dtype) / jnp.asarray(self.scale, dtype)

    # -- arithmetic (saturating, wide-accumulate) ---------------------------

    def add(self, a: jax.Array, b: jax.Array) -> jax.Array:
        wide = a.astype(jnp.int32) + b.astype(jnp.int32)
        return self._saturate(wide)

    def mul(self, a: jax.Array, b: jax.Array) -> jax.Array:
        """Qm.n * Qm.n -> Qm.n with int32 intermediate (hybrid precision:
        the product carries 2n fractional bits; shift back down)."""
        wide = a.astype(jnp.int32) * b.astype(jnp.int32)
        wide = _rounding_rshift(wide, self.frac_bits)
        return self._saturate(wide)

    def _saturate(self, wide: jax.Array) -> jax.Array:
        lo = -(2 ** (self.total_bits - 1))
        hi = 2 ** (self.total_bits - 1) - 1
        return jnp.clip(wide, lo, hi).astype(self.dtype)


def _rounding_rshift(x: jax.Array, bits: int) -> jax.Array:
    """Arithmetic right shift with round-to-nearest (ties away from zero is
    avoided; we add half-ulp before shifting, matching DPU-style fixed
    point)."""
    if bits == 0:
        return x
    half = jnp.asarray(1 << (bits - 1), x.dtype)
    return (x + half) >> bits


# Paper-representative formats.
Q1_14 = QFormat(int_bits=1, frac_bits=14)    # weights/features in [-2, 2)
Q3_12 = QFormat(int_bits=3, frac_bits=12)    # wider dynamic range
Q7_8 = QFormat(int_bits=7, frac_bits=8)      # int16 general purpose
Q1_6 = QFormat(int_bits=1, frac_bits=6)      # int8 features


# ---------------------------------------------------------------------------
# Dynamic symmetric quantization (per-tensor / per-axis scale)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Quantized:
    """A quantized tensor: ``values * scale`` reconstructs the original.

    ``scale`` broadcasts against ``values`` (per-tensor scalar or per-row
    column vector)."""

    values: jax.Array
    scale: jax.Array

    def dequantize(self, dtype=jnp.float32) -> jax.Array:
        # The product is formed in float32 and cast ONCE: casting the
        # scale to a narrow dtype first (bf16/f16) would round twice and
        # desynchronize this emulation from the mesh collectives, which
        # dequantize their int32 psum total in f32
        # (collectives.quantized_psum_ef) — the two must stay
        # bit-identical for the hop-size-1 parity tests to cover the
        # mesh path.
        return (self.values.astype(jnp.float32)
                * self.scale.astype(jnp.float32)).astype(dtype)


jax.tree_util.register_pytree_node(
    Quantized,
    lambda q: ((q.values, q.scale), None),
    lambda _, c: Quantized(*c),
)


def quantize_symmetric(x: jax.Array, bits: int = 8, axis=None,
                       stochastic: bool = False,
                       key: jax.Array | None = None) -> Quantized:
    """Symmetric linear quantization with dynamic scale.

    ``axis=None`` -> per-tensor scale; ``axis=k`` -> scale per slice along
    every axis except ``k``'s complement (i.e. reduce over ``axis``).
    Op by op (a fused program may round differently, and the mesh
    collectives must match this bit for bit); each float32 temporary the
    size of ``x`` is dropped as soon as the next op has consumed it, so a
    resident dataset holds at most two of them at once.
    """
    x = jnp.asarray(x, jnp.float32)
    qmax = 2 ** (bits - 1) - 1
    if axis is None:
        amax = jnp.max(jnp.abs(x))
    else:
        amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / qmax
    if stochastic:
        if key is None:
            raise ValueError("stochastic rounding requires a PRNG key")
        noise = jax.random.uniform(key, x.shape, jnp.float32)
        q = jnp.floor(x / scale + noise)
    else:
        q = jnp.round(x / scale)
    dtype = _INT_DTYPES[bits] if bits in _INT_DTYPES else jnp.int32
    q = jnp.clip(q, -qmax - 1, qmax)
    return Quantized(q.astype(dtype), scale)


def quantize_fixed_scale(x: jax.Array, scale: jax.Array,
                         bits: int = 8) -> Quantized:
    """Symmetric quantization against a *precomputed* scale.

    The out-of-core streaming path: a rotation window only sees a
    partition of the dataset, so the scale must come from a one-pass
    global statistic (``StreamingDataset.feature_absmax``) rather than
    the window's own max — otherwise every partition would quantize on
    its own grid and the streamed fit would diverge from the resident
    one.  With ``scale = max(|x|_global, 1e-12) / qmax`` this is
    bit-for-bit ``quantize_symmetric`` over the full dataset, gathered
    a partition at a time (same divide / round / clip sequence).
    """
    x = jnp.asarray(x, jnp.float32)
    qmax = 2 ** (bits - 1) - 1
    scale = jnp.asarray(scale, jnp.float32)
    q = jnp.round(x / scale)
    dtype = _INT_DTYPES[bits] if bits in _INT_DTYPES else jnp.int32
    return Quantized(jnp.clip(q, -qmax - 1, qmax).astype(dtype), scale)


_NP_INT_DTYPES = {8: _np.int8, 16: _np.int16, 32: _np.int32,
                  64: _np.int64}


def quantize_fixed_scale_np(x, scale, bits: int = 8) -> "_np.ndarray":
    """Numpy mirror of :func:`quantize_fixed_scale` — bit-identical
    integer output, zero JAX dispatch.

    The streaming workloads' ``stream_transform`` runs on the
    Prefetcher's worker thread, and a JAX execution issued there
    serializes behind the main thread's compiled training scan (see
    ``data.pipeline.PartitionRotation.schedule``).  Quantizing the
    window in numpy keeps the worker JAX-free: the gather buffer is
    divided / rounded / clipped on the host and only the int8/int16
    result is staged — the H2D transfer ships the narrow bytes, never a
    float32 window.

    Bit-parity holds because both paths run the same sequence in IEEE
    float32 — divide, round half-to-even (``np.round`` == XLA's
    ``round_nearest_even``), clip to ``[-qmax-1, qmax]``, narrow cast —
    and ``tests/test_pipeline.py`` pins it against random draws
    including exact .5 ties.
    """
    x = _np.asarray(x, _np.float32)
    qmax = 2 ** (bits - 1) - 1
    scale = _np.asarray(scale, _np.float32)
    q = _np.round(x / scale)
    dtype = _NP_INT_DTYPES.get(bits, _np.int32)
    return _np.clip(q, -qmax - 1, qmax).astype(dtype)


def symmetric_scale(amax, bits: int = 8) -> jax.Array:
    """The scale ``quantize_symmetric`` derives from an absmax — split
    out so host-computed global statistics quantize on exactly the
    same grid."""
    qmax = 2 ** (bits - 1) - 1
    return jnp.maximum(jnp.asarray(amax, jnp.float32), 1e-12) / qmax


def dequantize(q: Quantized, dtype=jnp.float32) -> jax.Array:
    return q.dequantize(dtype)


# ---------------------------------------------------------------------------
# Hybrid-precision linear algebra (narrow multiply, wide accumulate)
# ---------------------------------------------------------------------------

def fxp_matmul(a: jax.Array, b: jax.Array,
               acc_dtype=jnp.int32) -> jax.Array:
    """Integer matmul with wide accumulation: the paper's hybrid precision.

    ``a``: (..., M, K) int8/int16, ``b``: (K, N) int8/int16 ->
    (..., M, N) ``acc_dtype``.  On TPU this hits the MXU s8 path via
    ``preferred_element_type``; the pure-jnp semantics are identical.
    """
    return jax.lax.dot_general(
        a, b,
        dimension_numbers=(((a.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=acc_dtype,
    )


def int8_limbs(x: jax.Array):
    """``[(weight, limb)]`` decomposition into signed int8 limbs, most
    significant first: ``x == sum(weight * limb)``.

    This is the TPU-native widening trick (DESIGN.md §2): the MXU
    multiplies int8 operands natively (Mosaic has no int16 matmul), so a
    wide multiply is several 8-bit passes — structurally the DPU's
    software-widened multiply, run on the systolic array.  int8 passes
    through as one limb.  Wider ints split signed: each low limb is in
    [-128, 128) and its carry folds into the rest, so every limb is a
    true ``int8``.  Two signed limbs cover only [-32896, 32639], so an
    int16 takes three (the top one is 0 or 1).  Shared by ``hybrid_dot``
    (jnp path) and ``kernels.dispatch``'s Pallas path so the two stay
    bit-identical by construction.
    """
    if x.dtype == jnp.int8:
        return [(1.0, x)]
    if x.dtype not in (jnp.uint8, jnp.int16):
        raise TypeError(f"int8_limbs takes int8/uint8/int16, got {x.dtype}")
    n_limbs = 3 if x.dtype == jnp.int16 else 2
    rest = x.astype(jnp.int32)
    limbs = []
    for i in range(n_limbs - 1):
        lo = ((rest + 128) & 0xFF) - 128
        limbs.append((float(256 ** i), lo.astype(jnp.int8)))
        rest = (rest - lo) >> 8
    limbs.append((float(256 ** (n_limbs - 1)), rest.astype(jnp.int8)))
    return limbs[::-1]


def hybrid_dot(a: jax.Array, b: jax.Array, *, k_chunk: int = 4096
               ) -> jax.Array:
    """Overflow-safe integer matmul (..., M, K) x (K, N) -> float32.

    The paper's hybrid precision, adapted: every >8-bit operand is split
    into int8-range limbs, each limb pair is accumulated in int32 over
    K-chunks of ``k_chunk`` (bounding |partial| < 2^31), and limb partials
    are combined in float32.  Exact for |true dot| < 2^24 * 2^16.
    """
    K = a.shape[-1]
    k_chunk = min(k_chunk, K)          # never pad K *up* to the chunk
    n_chunks = -(-K // k_chunk)
    pad = n_chunks * k_chunk - K
    if pad:
        a = jnp.concatenate(
            [a, jnp.zeros(a.shape[:-1] + (pad,), a.dtype)], axis=-1)
        b = jnp.concatenate(
            [b, jnp.zeros((pad,) + b.shape[1:], b.dtype)], axis=0)

    out = None
    for wa, la in int8_limbs(a):
        for wb, lb in int8_limbs(b):
            acc = jnp.zeros(a.shape[:-1] + b.shape[1:], jnp.float32)
            for c in range(n_chunks):
                sl_a = la[..., c * k_chunk:(c + 1) * k_chunk]
                sl_b = lb[c * k_chunk:(c + 1) * k_chunk]
                part = jax.lax.dot_general(
                    sl_a, sl_b,
                    dimension_numbers=(((a.ndim - 1,), (0,)), ((), ())),
                    preferred_element_type=jnp.int32)
                acc = acc + part.astype(jnp.float32)
            term = (wa * wb) * acc
            out = term if out is None else out + term
    return out


def quantized_dot(xq: Quantized, wq: Quantized,
                  acc_dtype=jnp.int32, out_dtype=jnp.float32) -> jax.Array:
    """(M,K)q @ (K,N)q -> float: integer MXU matmul + scale fixup.

    Scales must be per-tensor or per-row(M)/per-col(N) so the fixup is a
    rank-1 broadcast (this is what per-channel quantization gives you)."""
    acc = fxp_matmul(xq.values, wq.values, acc_dtype)
    return acc.astype(out_dtype) * xq.scale.astype(out_dtype) * \
        wq.scale.astype(out_dtype)


# ---------------------------------------------------------------------------
# Error-feedback state for quantized gradient exchange (beyond-paper reuse
# of I1 for collective compression; see distributed/compression.py)
# ---------------------------------------------------------------------------

def ef_quantize(grad: jax.Array, error: jax.Array, bits: int = 8
                ) -> Tuple[Quantized, jax.Array]:
    """Quantize ``grad + error`` and return (quantized, new_error).

    Error feedback keeps the compressed-SGD iterates within O(1) of the
    exact ones (Karimireddy et al.); new_error = input - dequantized."""
    target = grad + error
    q = quantize_symmetric(target, bits=bits)
    new_error = target - q.dequantize(grad.dtype)
    return q, new_error


@partial(jax.jit, static_argnames=("bits",))
def quantize_dequantize(x: jax.Array, bits: int = 8) -> jax.Array:
    """Round-trip helper (used in tests/benchmarks for accuracy tables)."""
    return quantize_symmetric(x, bits=bits).dequantize(x.dtype)


def topk_keep(x: jax.Array, frac: float) -> jax.Array:
    """Zero all but the ``max(1, floor(size*frac))`` largest-|.| entries.

    This is THE top-k selection both compression layers share —
    ``distributed.compression.topk_sparsify`` (the mesh=None wire
    emulation) and ``distributed.collectives.sparse_psum_ef`` (the mesh
    collective) must keep identical numerics or the CPU tests stop
    covering the mesh path.  Selection is by index (``lax.top_k``), not
    by threshold comparison: a threshold mask keeps every tied entry,
    so e.g. an all-zero input would keep the whole leaf and the
    modeled ``wire_bytes`` (exactly k values + indices) would silently
    under-count the traffic.  Exactly k entries survive, always.
    """
    flat = x.reshape(-1)
    k = max(1, int(flat.size * frac))
    _, idx = jax.lax.top_k(jnp.abs(flat), k)
    mask = jnp.zeros(flat.shape, x.dtype).at[idx].set(1)
    return (flat * mask).reshape(x.shape)
