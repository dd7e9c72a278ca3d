"""Survivor-weighted hierarchical merges — training through dead lanes.

DESIGN — the masked merge
-------------------------
The exact cadence round (``merge_plan.cadence_round``) averages the
per-lane phase-end states uniformly: ``avg = Σ_l s_l / n``.  When lanes
die that average would either NaN (a dead lane's garbage propagates) or
bias toward zero (masking without renormalising).  The survivor merge
renormalises by the *surviving* lane count:

    avg = Σ_l m_l · s_l / n_s,      n_s = Σ_l m_l

with ``m`` a 0/1 mask riding the scan carry.  On the wire this is
expressed as a per-slow-hop-participant **delta**

    x_p = (Σ_{l∈p} m_l s_l − n_p · state) / n_s

so that ``Σ_p x_p = avg − state`` and a fully-dead participant
contributes an exactly-zero wire (``n_p = d_p = 0``) — nothing of a
dead pod's stale state leaks into the merge.  The new state is
``state + Σ_p x̂_p`` where ``x̂`` is the (optionally compressed)
transmitted wire.

EF conservation for dead participants: compressed wires gate on
``alive_p = n_p > 0`` (``collectives.quantized_psum_ef(..., alive=)``)
— a dead participant transmits zero and its error-feedback residual is
*held*, not dropped, so the mass re-enters the merge if the participant
revives (and the EF invariant Σ(wire + residual) = Σ target holds for
the survivors either way).

Metrics are masked-averaged the same way (``Σ m_l · metric_l / n_s``),
so a dead lane's loss no longer pollutes the history.

Non-float state leaves pass through the merge unchanged (frozen): the
averaging engine requires float state (see ``PimGrid.fit``), and the
minibatch counter is float32 by design, so this only affects exotic
custom states.

The runner family is cached on the grid exactly like the plan runners
(``merge_plan.cache_get``/``cache_put``), keyed by the step functions'
signatures, the cadence and the compression — arming a fault plan does
not recompile per round.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.distributed import collectives as coll
from repro.distributed import compression as comp
from repro.distributed import merge_plan as mp


def _wsum(tree, mask):
    """Mask-weighted sum over the leading lane axis."""
    def one(x):
        m = mask.reshape((mask.shape[0],) + (1,) * (x.ndim - 1))
        return jnp.sum(x * m.astype(x.dtype), axis=0)
    return jax.tree.map(one, tree)


def _float_leaf(x):
    return jnp.issubdtype(x.dtype, jnp.inexact)


def _wire_delta(ssum, state, n_local, n_s):
    """Per-participant wire: ``(Σ_local m·s − n_local·state) / n_s``.
    Summed over the slow hop this is ``masked_avg − state``; a dead
    participant's wire is exactly zero."""
    def one(ss, s):
        if not _float_leaf(s):
            return jnp.zeros_like(s)  # frozen leaf: no wire traffic
        return (ss - n_local.astype(s.dtype) * s) / n_s.astype(s.dtype)
    return jax.tree.map(one, ssum, state)


def _apply_delta(state, delta):
    return jax.tree.map(
        lambda s, d: s + d if _float_leaf(s) else s, state, delta)


def _gated_compress(wire, ef, compression, alive):
    """mesh=None slow-hop emulation with EF conservation for a dead
    hop: wire and residual are gated on the scalar ``alive``."""
    sq = jax.tree.map(lambda e: e[0], ef)
    deq, new = comp.ef_compress_tree(wire, sq, compression)
    deq = jax.tree.map(
        lambda d: jnp.where(alive, d, jnp.zeros_like(d)), deq)
    new = jax.tree.map(
        lambda n, e: jnp.where(alive, n, e), new, sq)
    return deq, jax.tree.map(lambda n: n[None], new)


def _slow_hop_compressed(wire, ef, compression, alive, slow):
    """Per-leaf compressed psum over the slow mesh axis, alive-gated so
    dead participants transmit zero and hold their EF residual."""
    flat, td = jax.tree.flatten(wire)
    flat_e = td.flatten_up_to(ef)
    outs, new_e = [], []
    for x, e in zip(flat, flat_e):
        if not comp._compressible(x):
            outs.append(jax.lax.psum(x, slow))
            new_e.append(e)
        elif compression.top_k_frac is not None:
            o, ne = coll.sparse_psum_ef(
                x, e[0], slow, frac=compression.top_k_frac,
                bits=compression.bits,
                error_feedback=compression.error_feedback, alive=alive)
            outs.append(o)
            new_e.append(ne[None])
        elif compression.error_feedback:
            o, ne = coll.quantized_psum_ef(
                x, e[0], slow, bits=compression.bits, alive=alive)
            outs.append(o)
            new_e.append(ne[None])
        else:
            gated = jnp.where(alive, x, jnp.zeros_like(x))
            outs.append(coll.quantized_psum(gated, slow,
                                            bits=compression.bits))
            new_e.append(e)
    return td.unflatten(outs), td.unflatten(new_e)


def survivor_runners(grid, local_fn, update_fn, *, merge_every: int,
                     compression=None) -> dict:
    """Jitted ``{"runner", "round"}`` for the masked merge family.

    Carry is ``(state, mask, ef)``: ``mask`` float32 ``(n_vdpus,)`` of
    0/1 survivor flags, ``ef`` the hop-leading error-feedback tree
    (state-shaped; carried even for exact wires so the carry layout —
    and hence the checkpoint layout — is rung-invariant under the
    recovery ladder).  ``runner(carry, data, length=L)`` scans ``L``
    rounds of ``merge_every`` local steps; metric leaves come back
    stacked ``(L, merge_every, ...)``.
    """
    from repro.kernels import dispatch as _dispatch

    key = ("survivor", mp.fn_signature(local_fn),
           mp.fn_signature(update_fn), _dispatch.kernels_enabled(),
           merge_every, compression)
    cached = mp.cache_get(grid, key)
    if cached is not None:
        return cached

    scale = float(grid.n_vdpus)

    def lanes_phase(state, data, mask):
        """k masked local steps; returns (Σ m·s, Σ m·metric, Σ m)."""
        def per_vdpu(sl):
            def local_step(st, _):
                part = jax.tree.map(lambda x: x * scale,
                                    local_fn(st, sl))
                return update_fn(st, part)
            return jax.lax.scan(local_step, state, None,
                                length=merge_every)

        states, metrics = jax.vmap(per_vdpu)(data)
        return (_wsum(states, mask), _wsum(metrics, mask),
                jnp.sum(mask))

    inv_metrics = 1.0 / scale

    if grid.mesh is None:
        def round_body(carry, data):
            state, mask, ef = carry
            ssum, msum, n_local = lanes_phase(state, data, mask)
            n_s = jnp.maximum(n_local, 1.0)
            alive = n_local > 0
            wire = _wire_delta(ssum, state, n_local, n_s)
            if compression is None:
                delta, ef = wire, ef
            else:
                delta, ef = _gated_compress(wire, ef, compression,
                                            alive)
            new_state = _apply_delta(state, delta)
            metrics = jax.tree.map(
                lambda m: m / n_s.astype(m.dtype) if _float_leaf(m)
                else m, msum)
            return (new_state, mask, ef), metrics
    else:
        axes = tuple(grid.data_axes)
        slow = axes[0]

        def shard_body(state, mask, ef, data):
            ssum, msum, n_local = lanes_phase(state, data, mask)
            part = (ssum, msum, n_local)
            for ax in reversed(axes[1:]):
                part = jax.tree.map(
                    lambda x, a=ax: jax.lax.psum(x, a), part)
            ssum, msum, n_fast = part
            n_s = jnp.maximum(jax.lax.psum(n_fast, slow), 1.0)
            alive = n_fast > 0
            wire = _wire_delta(ssum, state, n_fast, n_s)
            if compression is None:
                delta = jax.tree.map(
                    lambda x: jax.lax.psum(x, slow), wire)
            else:
                delta, ef = _slow_hop_compressed(wire, ef, compression,
                                                 alive, slow)
            new_state = _apply_delta(state, delta)
            msum = jax.tree.map(lambda x: jax.lax.psum(x, slow), msum)
            metrics = jax.tree.map(
                lambda m: m / n_s.astype(m.dtype) if _float_leaf(m)
                else m, msum)
            return new_state, ef, metrics

        espec_of = lambda ef: jax.tree.map(  # noqa: E731
            lambda _: mp._ef_spec(grid), ef)

        def round_body(carry, data):
            state, mask, ef = carry
            data_specs = jax.tree.map(lambda _: P(axes), data)
            new_state, ef, metrics = shard_map(
                shard_body, mesh=grid.mesh,
                in_specs=(P(), P(axes), espec_of(ef), data_specs),
                out_specs=(P(), espec_of(ef), P()),
                check_vma=False)(state, mask, ef, data)
            return (new_state, mask, ef), metrics

    del inv_metrics  # masked mean replaces the uniform 1/n scaling

    donate = (0,) if mp.donating_backend() else ()

    @partial(jax.jit, static_argnames=("length",),
             donate_argnums=donate)
    def runner(carry, data, *, length: int):
        return jax.lax.scan(
            lambda c, _: round_body(c, data), carry, None,
            length=length)

    @jax.jit
    def round_fn(carry, data):
        return round_body(carry, data)

    runners = {"runner": runner, "round": round_fn}
    mp.cache_put(grid, key, runners, local_fn, update_fn)
    return runners


def init_mask(grid):
    """All-survivors mask, replicated/sharded to match the carry spec."""
    mask = jnp.ones((grid.n_vdpus,), jnp.float32)
    if grid.mesh is not None:
        from jax.sharding import NamedSharding
        spec = NamedSharding(grid.mesh, P(tuple(grid.data_axes)))
        mask = jax.device_put(mask, spec)
    return mask


def place_mask(grid, mask_host):
    """Host numpy mask -> device mask with the grid's sharding."""
    mask = jnp.asarray(mask_host, jnp.float32)
    if grid.mesh is not None:
        from jax.sharding import NamedSharding
        spec = NamedSharding(grid.mesh, P(tuple(grid.data_axes)))
        mask = jax.device_put(mask, spec)
    return mask
