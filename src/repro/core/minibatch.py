"""On-device minibatch sampling for the PIM scan engine.

The paper trains full-batch: every iteration streams a DPU's whole
resident partition (insight I3).  PIM-Opt (arXiv 2404.07164) shows that
on real UPMEM hardware the interesting distributed-optimization axis is
*minibatch* SGD with local update cadence — each DPU samples a batch
from its resident rows, takes a local step, and the host merge runs at
cadence k.  This module adds that axis to the engine without touching
it: minibatching is a pure transformation of the ``(local_fn,
update_fn, init_state)`` triple ``PimGrid.fit`` consumes, so every
engine path (scan/python, any cadence, overlap, compression) composes
with it unchanged.

DESIGN — the sampler schedule
-----------------------------

* **on-device, deterministic** — the batch for local step ``t`` is a
  function of ``(seed, t)`` only.  A step counter rides in the scan
  carry next to the model state (as a float32 scalar, so cadence
  averaging keeps it exact — every vDPU advances it identically), and
  the per-epoch permutation is drawn inside the traced step from
  ``fold_in(seed, epoch)``.  No host-side cursor: replaying a step
  replays its batch, which is what makes Trainer restarts bit-exact.
* **epoch-exact coverage** — an epoch is ``E = ceil(per/b)`` steps over
  a fresh permutation of the ``per`` resident row slots, partitioned
  into ``E`` batches of static size ``b``.  When ``b`` does not divide
  ``per`` the last batch is padded with repeated indices carrying a
  zero *schedule mask*, so every resident slot contributes exactly
  once per epoch window (the property test in ``tests/test_minibatch``
  pins this).
* **unbiased scaling** — the batch partial is scaled by
  ``per / n_valid`` (``n_valid`` = unpadded entries in this batch), so
  it is an unbiased estimator of the full-batch partial and the
  ``update_fn`` normalisation (which divides by the global row count)
  needs no change.  With ``b == per`` the schedule degenerates to the
  full partition and the scale to 1 — but callers should pass
  ``batch_size=None`` for full batch, which bypasses this module
  entirely (the bit-exact path).
* **shared schedule** — all vDPUs use the same permutation of their
  *slot indices*; the rows behind those slots differ per vDPU (the
  resident placement), so the sampled data still differs per vDPU
  exactly as PIM-Opt's per-DPU partition sampling does.

The counter is only exact when the merge commit is the plain average
(``avg(lane counters) == counter + k`` bit-for-bit, and the overlap
delta-commit adds exactly ``k``).  A stateful outer optimizer (SlowMo,
Nesterov) would fold the counter's delta into its momentum and walk it
off the integer grid — the workload layer (``core.mlalgos.api``)
refuses that combination with a clear error.
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def epoch_steps(rows_per_vdpu: int, batch_size: int) -> int:
    """Steps per epoch window: ``ceil(rows_per_vdpu / batch_size)``."""
    return -(-rows_per_vdpu // batch_size)


def batch_indices(rows_per_vdpu: int, batch_size: int, seed: int,
                  step) -> Tuple[jax.Array, jax.Array]:
    """The schedule: ``(indices (b,), valid-mask (b,))`` for local step
    ``step``.  Traceable (``step`` may be a traced scalar) and eager
    (tests call it per-step as the coverage oracle — it is the single
    definition of the schedule, so the oracle cannot drift from the
    engine)."""
    per, b = rows_per_vdpu, batch_size
    E = epoch_steps(per, b)
    pad = E * b - per
    step = jnp.asarray(step, jnp.int32)
    epoch = step // E
    pos = step % E
    key = jax.random.fold_in(jax.random.PRNGKey(seed), epoch)
    perm = jax.random.permutation(key, per).astype(jnp.int32)
    if pad:
        perm = jnp.concatenate([perm, perm[:pad]])
    valid = (jnp.arange(E * b) < per).astype(jnp.float32)
    idx = jax.lax.dynamic_slice(perm, (pos * b,), (b,))
    mask = jax.lax.dynamic_slice(valid, (pos * b,), (b,))
    return idx, mask


def host_schedule(rows_per_vdpu: int, batch_size: int, seed: int,
                  step: int, *, shuffle: bool = True
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Eager (numpy) view of :func:`batch_indices` — THE schedule
    shared by the on-device sampler and the host-side partition
    rotation (``data/pipeline``).  Rotation window ``t`` holds exactly
    the resident slots ``batch_indices(per, part, seed, t)`` names, so
    epoch-exact coverage composes across the two levels and streaming
    fits are bit-for-bit the fully-resident minibatch fit with the same
    seed.

    ``shuffle=False`` replaces the per-epoch ``fold_in(seed, epoch)``
    permutation with the identity (sequential tiling — the layout
    where a single-window stream is bit-for-bit the fully-resident
    full-batch fit).  The device sampler has no sequential mode; this
    knob exists only at the rotation level.
    """
    per, b = rows_per_vdpu, batch_size
    if shuffle:
        idx, mask = batch_indices(per, b, seed, step)
        return np.asarray(idx), np.asarray(mask)
    E = epoch_steps(per, b)
    pad = E * b - per
    perm = np.arange(per, dtype=np.int32)
    if pad:
        perm = np.concatenate([perm, perm[:pad]])
    valid = (np.arange(E * b) < per).astype(np.float32)
    pos = int(step) % E
    return perm[pos * b:(pos + 1) * b], valid[pos * b:(pos + 1) * b]


def minibatch_fns(local_fn: Callable, update_fn: Callable,
                  init_state: Any, *, rows_per_vdpu: int,
                  batch_size: int, seed: int = 0):
    """Wrap an engine triple so each local step sees a sampled batch.

    Returns ``(local_fn', update_fn', init_state', unwrap)`` where the
    wrapped state is ``(state, step_counter)`` and ``unwrap`` recovers
    the caller's state tree.  ``local_fn`` must follow the
    ``shard_rows`` slice convention (a dict with a per-row ``"w"``
    mask) — the schedule mask composes into ``"w"`` so padded schedule
    slots contribute nothing, exactly like shard padding.  A trailing
    array operand (``PimGrid.fit``'s ``consts``) passes through to both
    functions.
    """
    per, b = rows_per_vdpu, batch_size
    if not 1 <= b <= per:
        raise ValueError(
            f"batch_size must be in [1, rows_per_vdpu={per}], got {b}")

    def sample_local_fn(carry, sl, *consts):
        state, t = carry
        # the counter is float32 for merge-averaging; it holds exact
        # integers (each step adds 1.0, each merge averages identical
        # lane values), so the round-trip back to int is exact
        idx, mask = batch_indices(per, b, seed,
                                  jnp.round(t).astype(jnp.int32))
        batch = {k: jnp.take(v, idx, axis=0) for k, v in sl.items()}
        batch["w"] = batch["w"] * mask
        part = local_fn(state, batch, *consts)
        # unbiased estimate of the full-partition statistic: E[scale *
        # sum over batch] = sum over partition (n_valid = b except on
        # the padded last batch of an epoch)
        scale = per / jnp.maximum(jnp.sum(mask), 1.0)
        return jax.tree.map(lambda x: x * scale, part)

    def sample_update_fn(carry, merged, *consts):
        state, t = carry
        new_state, metrics = update_fn(state, merged, *consts)
        return (new_state, t + 1.0), metrics

    wrapped0 = (init_state, jnp.zeros((), jnp.float32))
    return sample_local_fn, sample_update_fn, wrapped0, lambda c: c[0]
