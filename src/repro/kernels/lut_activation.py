"""LUT activation Pallas TPU kernel — the paper's insight I2, TPU-native.

The DPU version gathers scalar table entries from WRAM.  A systolic
machine wants matrix work, so the kernel evaluates the nearest-entry
lookup as a one-hot matmul on the MXU with the table resident in VMEM,
in one bf16 pass that is still exact:

* the wrapper splits the f32 table into three bf16 parts
  (``split_table``) with ``(hi + mid) + lo == table``.  A 0/1 one-hot
  times a bf16 part, accumulated in f32, is that part's entry exactly,
  so one DEFAULT-precision pass and two f32 adds give the f32 entry;
* the entry index is split as ``idx = 128 a + b``.  The one-hot of
  ``b`` is built transposed, ``(128, lanes)``, from a lane-dense row of
  inputs, so ``parts^T (3A, 128) @ onehot_b^T`` contracts over one MXU
  tile whatever the table's length, and a sublane compare-and-sum picks
  row ``a`` of each column.

The lookup is elementwise, so the wrapper reads any input as
lane-dense rows of whole 512-lane tiles, as a view where the array
already lies so (full-batch GD's ``(vDPUs, 8192)`` logits; a minibatch's
``(vDPUs, 64)``, transposed), else flattened, in blocks cut by its size.
A batching rule applies the kernel once to a vmapped array: the
training step vmaps the sigmoid over the vDPUs, and a vmapped
``pallas_call`` would add one grid slice per vDPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# b = idx % 128 spans one MXU tile of the contraction
_K = 128
# lanes of a kernel block
_WIDTH = 512
# rows of a block: 512 KiB of f32 input a block
_MAX_BLOCK_ROWS = 256


def split_table(table: jax.Array):
    """The f32 table as three bf16 parts ``(hi, mid, lo)`` with
    ``(hi + mid) + lo == table`` in f32 (each part rounds what the
    parts before it leave over)."""
    t = table.astype(jnp.float32)
    hi = t.astype(jnp.bfloat16)
    rest = t - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _parts_matrix(table: jax.Array) -> tuple[jax.Array, int]:
    """``(3 A + pad, 128)`` bf16: row ``p A + a`` holds part ``p`` of
    entries ``128 a .. 128 a + 127``; ``A`` (returned) is the number of
    128-entry rows rounded up to whole sublane tiles, and the rows are
    padded to whole bf16 tiles."""
    n_entries = table.shape[0]
    n_a = pl.cdiv(pl.cdiv(n_entries, _K), 8) * 8
    parts = jnp.stack(split_table(table))                    # (3, E)
    parts = jnp.pad(parts, ((0, 0), (0, n_a * _K - n_entries)))
    parts = parts.reshape(3 * n_a, _K)
    return jnp.pad(parts, ((0, -(3 * n_a) % 16), (0, 0))), n_a


def _lut_kernel(x_ref, parts_ref, o_ref, *, x_min: float, step: float,
                n_entries: int, n_a: int):
    rows, width = x_ref.shape
    parts = parts_ref[...]
    b_iota = jax.lax.broadcasted_iota(jnp.int32, (_K, width), 0)
    a_iota = jax.lax.broadcasted_iota(jnp.int32, (n_a, width), 0)
    r_iota = jax.lax.broadcasted_iota(jnp.int32, (8, width), 0)

    def eight_rows(g, carry):
        r0 = pl.multiple_of(g * 8, 8)
        x = x_ref[pl.ds(r0, 8), :]
        idx = jnp.clip(jnp.round((x - x_min) / step), 0, n_entries - 1
                       ).astype(jnp.int32)
        a, b = idx >> 7, idx & (_K - 1)     # idx = 128 a + b
        out = jnp.zeros((8, width), jnp.float32)
        for r in range(8):
            onehot = (b_iota == b[r:r + 1]).astype(jnp.bfloat16)
            cols = jax.lax.dot_general(
                parts, onehot, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            pick = a_iota == a[r:r + 1]
            hi, mid, lo = (
                jnp.sum(jnp.where(pick, cols[p * n_a:(p + 1) * n_a], 0.0),
                        axis=0, keepdims=True)
                for p in range(3))
            out = jnp.where(r_iota == r, (hi + mid) + lo, out)
        o_ref[pl.ds(r0, 8), :] = out
        return carry

    jax.lax.fori_loop(0, rows // 8, eight_rows, 0)


def _lane_dense(x: jax.Array) -> jax.Array:
    """``x`` as 2-D f32 rows of whole ``_WIDTH``-lane tiles: its own last
    axis where that is one (a view, no copy), else flattened into rows
    of ``_WIDTH`` (a small vector, or one that fills no lane tile)."""
    x = x.astype(jnp.float32)
    if x.ndim >= 2 and x.shape[-1] % _WIDTH == 0:
        return x.reshape(-1, x.shape[-1])
    flat = jnp.pad(x.reshape(-1), (0, -x.size % _WIDTH))
    return flat.reshape(-1, _WIDTH)


def _blocks(rows: int) -> tuple[int, int]:
    """(block rows, blocks): the fewest blocks of at most
    ``_MAX_BLOCK_ROWS`` rows, of equal whole-tile height, so that
    padding stays under 8 rows a block."""
    n_blocks = pl.cdiv(rows, _MAX_BLOCK_ROWS)
    return pl.cdiv(pl.cdiv(rows, n_blocks), 8) * 8, n_blocks


# jitted so that a program traced again (a new closure, the same shapes)
# reuses the kernel's trace
@functools.partial(jax.jit, static_argnames=("x_min", "x_max", "interpret"))
def _lut_call(x: jax.Array, table: jax.Array, *, x_min: float,
              x_max: float, interpret: bool) -> jax.Array:
    if x.ndim == 2 and x.shape[1] % _WIDTH and not x.shape[0] % _WIDTH:
        # XLA lays a 2-D array whose last axis fills no lane tile out
        # transposed, its long axis on the lanes: read it as it lies
        return _lut_call(x.T, table, x_min=x_min, x_max=x_max,
                         interpret=interpret).T
    n_entries = table.shape[0]
    step = (x_max - x_min) / (n_entries - 1)
    parts, n_a = _parts_matrix(table)
    x2 = _lane_dense(x)
    rows, cols = x2.shape
    bm, n_blocks = _blocks(rows)
    x2 = jnp.pad(x2, ((0, n_blocks * bm - rows), (0, 0)))

    kernel = functools.partial(_lut_kernel, x_min=x_min, step=step,
                               n_entries=n_entries, n_a=n_a)
    out = pl.pallas_call(
        kernel,
        grid=(n_blocks, cols // _WIDTH),
        in_specs=[
            pl.BlockSpec((bm, _WIDTH), lambda i, j: (i, j)),
            pl.BlockSpec(parts.shape, lambda i, j: (0, 0)),  # VMEM-resident
        ],
        out_specs=pl.BlockSpec((bm, _WIDTH), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(x2.shape, jnp.float32),
        interpret=interpret,
        name="lut_activation",
        metadata={"kernel": "lut_activation"},
    )(x2, parts)
    out = out[:rows].reshape(-1)[:x.size]
    return out.reshape(x.shape).astype(x.dtype)


def lut_activation(x: jax.Array, table: jax.Array, *, x_min: float,
                   x_max: float, interpret: bool = False) -> jax.Array:
    """Nearest-entry LUT evaluation of ``x`` (any shape): the entry at
    ``clip(round((x - x_min) / step), 0, n - 1)`` in ``x.dtype``, equal
    to ``ref.lut_activation_ref`` (a ``-0.0`` entry comes back ``0.0``:
    the MXU's sum of a one-hot row drops the sign of zero).

    Under ``jax.vmap`` the kernel runs once over the whole batched
    ``x``; the table must not be batched."""

    @jax.custom_batching.custom_vmap
    def lookup(x, table):
        return _lut_call(x, table, x_min=x_min, x_max=x_max,
                         interpret=interpret)

    @lookup.def_vmap
    def _whole_batch(axis_size, in_batched, x, table):
        x_batched, table_batched = in_batched
        if table_batched:
            raise NotImplementedError(
                "lut_activation: one table for the whole batch")
        return lookup(x, table), x_batched

    return lookup(x, table)
