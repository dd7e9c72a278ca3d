"""PimGrid — the paper's PIM execution model as a composable JAX module.

The UPMEM system the paper evaluates is a grid of 2,524 DPUs, each a weak
core bonded to its own DRAM bank.  Training works like this (paper §ML
implementations):

  1. the training set is partitioned *once* across DPU banks and stays
     resident there for the whole run (insight I4),
  2. every iteration, each DPU computes a *partial statistic* (gradient,
     histogram, cluster sums) over its rows, streaming its bank (I3),
  3. DPUs cannot communicate; the host CPU gathers and merges the partial
     results and broadcasts the updated model (I5),
  4. merge cost is tolerable when overlapped with compute (I5).

TPU mapping (DESIGN.md §2): a *virtual DPU* (vDPU) is one slice of a leading
``n_vdpus`` axis.  That axis is sharded over the mesh's data axes
(``("pod","data")`` in production), and vDPUs co-resident on one device are
vmapped — exactly like UPMEM tasklets.  The host merge becomes a
*hierarchical* reduction: ``psum`` over ``data`` (fast ICI, = intra-rank
merge) followed by ``psum`` over ``pod`` (slow DCN, = the host hop).

``PimGrid`` runs in two modes with one code path:
  * ``mesh=None`` — single-device (CPU tests / benchmarks): vmap + sum.
  * ``mesh=...``  — ``shard_map`` over the data axes, hierarchical psum.

DESIGN — the scan step engine
-----------------------------

``fit`` compiles the whole iterative loop instead of dispatching one
jitted step per Python iteration (which re-creates the paper's
CPU-centric bottleneck: the host dominates while the grid idles):

  * **scan chunks** — steps run as ``jax.lax.scan`` over chunks of
    ``scan_chunk`` iterations.  One host dispatch per chunk; metrics for
    every step inside the chunk come back stacked, so per-step history
    and callbacks still stream out at chunk boundaries.  Callbacks see
    per-step metrics but end-of-chunk state (intermediate states are
    never materialized).
  * **donated carry** — on backends with buffer donation (TPU/GPU) the
    carried state is donated to the chunk runner, so the model update is
    in-place bank-resident state, like the DPU's.  ``fit`` copies the
    caller's ``init_state`` before the first chunk, but state handed to
    callbacks is live carry: its buffers are consumed by the next
    chunk's dispatch, so callbacks that retain state must copy it.
  * **compile cache** — the jitted chunk runner is cached on the grid
    keyed by ``(local_fn, update_fn)``; repeated ``fit`` calls with the
    same functions never retrace (at most two traces per pair: the full
    chunk and the remainder chunk).
  * **kernel dispatch** — the mlalgos' inner loops route through
    ``repro.kernels.dispatch`` (fxp_matmul / kmeans_assign / split_hist /
    lut_activation), so the body the scan compiles is the same code the
    TPU runs natively; ``engine="python"`` keeps the seed's per-step
    loop as the parity oracle.

DESIGN — merge cadence (``merge_every``)
----------------------------------------

The paper's strong-scaling table shows the host merge dominating once
per-DPU work shrinks; PIM-Opt (arXiv 2404.07164) makes the *cadence* of
that merge a first-class axis.  ``fit(..., merge_every=k)`` runs ``k``
local update steps per vDPU between merges:

  * each vDPU carries its **own copy of the state** and applies
    ``update_fn`` to its *local* partial statistics, scaled by
    ``n_vdpus`` so the shard looks like the whole dataset to the
    normalisation inside ``update_fn`` (the local-SGD view: a vDPU
    optimises on its resident rows as if they were everything),
  * after ``k`` local steps the per-vDPU states are **averaged** with
    the same hierarchical reduction as ``map_reduce`` (vmap-lane sum →
    ICI psum → pod psum, i.e. tasklet → rank → host) and the averaged
    state is re-broadcast — one merge per ``k`` steps instead of one
    per step,
  * per-local-step metrics are averaged across vDPUs with the same
    tree; combined with the ``n_vdpus`` pre-scaling this reproduces the
    global normalisation exactly (``mean_v(V·m_v/n) = Σ_v m_v / n``),
  * ``merge_every=1`` takes the *original* merge-per-step code path —
    it is bit-exact with the PR 1 engine by construction, and serves as
    the parity oracle for cadence sweeps,
  * states must be float pytrees when ``merge_every > 1`` (averaging
    integer state would truncate); metrics report the loss of the
    *divergent local models*, which converges to the global loss as the
    states re-sync each round.

``steps`` always counts **local update steps**; a trailing
``steps % k`` remainder runs as one short round (its runner is cached
under its own ``merge_every`` key).  With ``merge_every=k`` the scanned
unit is one merge *round*, so ``scan_chunk`` counts rounds, not steps.

DESIGN — merge plans (``merge_plan``)
-------------------------------------

Everything beyond the exact default — the overlapped double-buffered
merge, int8/top-k error-feedback wire compression, SlowMo outer
momentum, adaptive cadence — composes as a
``repro.distributed.merge_plan.MergePlan`` and is implemented there.
``fit(merge_plan=...)`` is the canonical spelling; the legacy
``merge_every= / overlap_merge= / merge_compression=`` kwargs are thin
constructors for the equivalent plan.  A default plan (all knobs off)
runs the engine in this file unchanged — bit-exact with the pre-plan
releases by construction.
"""

from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.resilience import faults as _faults


def _tree_sum_leading(tree):
    return jax.tree.map(lambda x: jnp.sum(x, axis=0), tree)


@dataclasses.dataclass(frozen=True)
class PimGrid:
    """A grid of virtual DPUs over (optionally) a device mesh.

    Args:
      n_vdpus: number of virtual DPUs (>= product of data-axis sizes, and
        divisible by it when a mesh is used).
      mesh: optional ``jax.sharding.Mesh``; when given, the vDPU axis is
        sharded over ``data_axes`` and reductions are hierarchical psums.
      data_axes: mesh axes carrying the vDPU shards, ordered slow->fast
        (the *first* axis is the "host hop" — reduced last, compressible).
    """

    n_vdpus: int
    mesh: Mesh | None = None
    data_axes: Sequence[str] = ("data",)
    # jitted chunk runners keyed by (local_fn, update_fn) — excluded from
    # eq/hash; mutated in place (the dataclass is frozen, the dict is not)
    _fit_cache: dict = dataclasses.field(default_factory=dict, init=False,
                                         repr=False, compare=False)

    def __post_init__(self):
        if self.mesh is not None:
            shards = self.n_shards
            if self.n_vdpus % shards:
                raise ValueError(
                    f"n_vdpus={self.n_vdpus} not divisible by data shards "
                    f"{shards}")

    # -- layout --------------------------------------------------------

    @property
    def n_shards(self) -> int:
        if self.mesh is None:
            return 1
        return int(np.prod([self.mesh.shape[a] for a in self.data_axes]))

    def data_sharding(self) -> NamedSharding | None:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P(tuple(self.data_axes)))

    def replicated_sharding(self) -> NamedSharding | None:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, P())

    def shard_rows(self, X: jax.Array, *extras: jax.Array):
        """Partition rows across vDPUs (the one-time resident placement).

        Pads the row count up to a multiple of ``n_vdpus`` and returns
        ``(data_dict, n_rows)`` where ``data_dict`` holds ``X`` (and
        positional extras ``y0``, ``y1``...) reshaped to
        ``(n_vdpus, rows_per_vdpu, ...)`` plus a 0/1 ``w`` mask marking
        real rows — local statistics must be weighted by ``w`` so padding
        never contaminates the merge.

        On a mesh every device receives only its own vDPUs' rows: a
        device array is padded and reshaped by one program whose output
        is laid out on the mesh, and a host array is sliced on the host
        per device.  The whole dataset is never staged on one device.
        """
        n = X.shape[0]
        per = -(-n // self.n_vdpus)              # ceil
        pad = per * self.n_vdpus - n

        def layout(a, xp):
            if pad:
                a = xp.concatenate(
                    [a, xp.zeros((pad,) + a.shape[1:], a.dtype)], axis=0)
            return a.reshape((self.n_vdpus, per) + a.shape[1:])

        def place(a):
            if self.mesh is None:
                return layout(jnp.asarray(a), jnp)
            sharding = self.data_sharding()
            mesh_devices = set(self.mesh.devices.flat)
            if isinstance(a, jax.Array) and not (
                    a.committed and set(a.devices()) - mesh_devices):
                return jax.jit(partial(layout, xp=jnp),
                               out_shardings=sharding)(a)
            host = layout(np.asarray(a), np)
            return jax.make_array_from_callback(
                host.shape, sharding, lambda idx: host[idx])

        # place() appends `pad` zero rows — zeros are exactly the mask
        # value for padding, so the mask goes in unpadded
        w = jnp.ones((n,), jnp.float32)
        data = {"X": place(X), "w": place(w)}
        for i, e in enumerate(extras):
            data[f"y{i}"] = place(e)
        return data, n

    # -- the core primitive ---------------------------------------------

    def map_reduce(self, local_fn: Callable[[Any, Any], Any],
                   model: Any, data: Any) -> Any:
        """partial = local_fn(model, per_vdpu_slice); return Σ partial.

        ``local_fn`` sees one vDPU's resident slice (no leading axis) and
        returns a pytree of summable statistics.  The reduction is the
        paper's host merge: vmapped-tasklet sum -> intra-pod psum -> pod
        psum.

        Example — a masked global sum (padding rows carry ``w == 0`` and
        contribute nothing):

        >>> import jax.numpy as jnp
        >>> from repro.core.pim import make_cpu_grid
        >>> grid = make_cpu_grid(4)
        >>> data, n = grid.shard_rows(jnp.arange(8.0)[:, None])
        >>> out = grid.map_reduce(
        ...     lambda w, sl: {"s": jnp.sum(sl["X"] * sl["w"][:, None])},
        ...     None, data)
        >>> float(out["s"])
        28.0
        """
        if self.mesh is None:
            return _tree_sum_leading(jax.vmap(lambda d: local_fn(model, d))(data))

        axes = tuple(self.data_axes)

        def shard_body(model, data):
            part = _tree_sum_leading(jax.vmap(lambda d: local_fn(model, d))(data))
            # Hierarchical merge: fast axes first (ICI), slow axis last
            # (the "host" hop). Mathematically one psum; structurally two
            # collectives with different replica groups (see roofline).
            for ax in reversed(axes[1:]):
                part = jax.tree.map(lambda x, a=ax: jax.lax.psum(x, a), part)
            part = jax.tree.map(lambda x: jax.lax.psum(x, axes[0]), part)
            return part

        data_specs = jax.tree.map(lambda _: P(axes), data)
        return shard_map(
            shard_body, mesh=self.mesh,
            in_specs=(P(), data_specs), out_specs=P(),
            check_vma=False,
        )(model, data)

    # -- merge-plan delegation ------------------------------------------
    #
    # The merge machinery (cadence rounds, the overlapped/compressed
    # pipeline, outer optimizers, adaptive cadence) lives in
    # ``repro.distributed.merge_plan`` — imported lazily because that
    # layer sits above core (it duck-types this grid).  The thin
    # wrappers below keep the public wire-layout API on the grid.

    def merge_wire_spec(self, local_fn: Callable, update_fn: Callable,
                        state: Any, data: Any, *, merge_every: int = 1):
        """ShapeDtypeStruct tree of what crosses the host hop per merge
        round — see ``distributed.merge_plan.wire_spec``."""
        from repro.distributed import merge_plan as mp
        return mp.wire_spec(self, local_fn, update_fn, state, data,
                            merge_every=merge_every)

    def init_merge_error(self, wire_spec: Any) -> Any:
        """Zero error-feedback buffer for a wire tree — see
        ``distributed.merge_plan.init_merge_error``."""
        from repro.distributed import merge_plan as mp
        return mp.init_merge_error(self, wire_spec)

    # -- generic training loop -------------------------------------------

    def make_runner(self, local_fn: Callable, update_fn: Callable, *,
                    merge_every: int = 1):
        """The cached jitted chunk runner for ``(local_fn, update_fn)``.

        ``runner(state, data, length=L)`` scans L merge rounds and
        returns ``(state, stacked_metrics)``.  At ``merge_every=1`` a
        round is one merge->update step and metric leaves come back
        shaped ``(L, ...)``; at cadence ``k > 1`` a round is ``k``
        vDPU-local steps plus one state merge and metric leaves are
        ``(L, k, ...)``.  ``length`` is static, so a fit sees at most
        two traces per cadence (chunk + remainder).

        Compile-cache keying rules: the runner is cached on the grid
        keyed by

          * the *signatures* of ``local_fn``/``update_fn`` — code object
            plus captured closure-cell and default-arg values (primitives
            by value, arrays/objects by identity).  ``train_*`` re-creates
            its closures each call; same code + same captured values
            still hit the cache, while a changed hyperparameter
            (``lr=lr`` closure or default binding) forces a new trace,
          * the trace-time ``kernels.dispatch`` flag — a runner traced
            with Pallas kernels on never serves a ``use_kernels(False)``
            fit,
          * ``merge_every`` — each cadence compiles its own round body.

        The cache is a bounded LRU (``merge_plan._FIT_CACHE_MAX``
        entries): paths whose closures capture fresh arrays per call
        (the quantized mlalgos) never repeat a key and would otherwise
        pin compiled executables forever.

        Example — repeated requests reuse the runner, a different
        cadence gets its own:

        >>> import jax.numpy as jnp
        >>> from repro.core.pim import make_cpu_grid
        >>> grid = make_cpu_grid(4)
        >>> def local_fn(w, sl):
        ...     return {"g": jnp.sum(sl["X"] * sl["w"][:, None], axis=0)}
        >>> def update_fn(w, merged):
        ...     return w - 0.1 * merged["g"], {}
        >>> runner = grid.make_runner(local_fn, update_fn)
        >>> grid.make_runner(local_fn, update_fn) is runner
        True
        >>> r4 = grid.make_runner(local_fn, update_fn, merge_every=4)
        >>> r4 is runner
        False
        """
        # The kernel-dispatch flag is read at trace time, so it is part of
        # the signature: a runner traced with kernels on must not serve a
        # use_kernels(False) fit.  Imported lazily — dispatch and
        # merge_plan sit above core in the layering.
        from repro.kernels import dispatch as _dispatch
        from repro.distributed import merge_plan as mp

        if merge_every < 1:
            raise ValueError(
                f"merge_every must be >= 1, got {merge_every}")

        key = (mp.fn_signature(local_fn), mp.fn_signature(update_fn),
               _dispatch.kernels_enabled(), merge_every)
        cached = mp.cache_get(self, key)
        if cached is not None:
            return cached

        # Donation is a no-op (with a warning) on CPU — only request
        # it where the runtime can actually alias the carry.
        donate = (0,) if mp.donating_backend() else ()

        @partial(jax.jit, static_argnames=("length",),
                 donate_argnums=donate)
        def runner(state, data, *, length: int):
            if merge_every == 1:
                # the PR 1 merge-per-step body, unchanged — cadence 1 is
                # bit-exact with the pre-cadence engine by construction
                def body(state, _):
                    merged = self.map_reduce(local_fn, state, data)
                    return update_fn(state, merged)
            else:
                def body(state, _):
                    return mp.cadence_round(self, local_fn, update_fn,
                                            merge_every, state, data)

            return jax.lax.scan(body, state, None, length=length)

        mp.cache_put(self, key, runner, local_fn, update_fn)
        return runner

    def compiled_step(self, local_fn: Callable, update_fn: Callable):
        """Deprecated pre-cadence alias — use :meth:`make_runner`."""
        warnings.warn(
            "PimGrid.compiled_step is deprecated; use "
            "PimGrid.make_runner(local_fn, update_fn) instead",
            DeprecationWarning, stacklevel=2)
        return self.make_runner(local_fn, update_fn)

    def fit(self, *, init_state: Any, local_fn: Callable,
            update_fn: Callable, data: Any, steps: int,
            callback: Callable | None = None,
            scan_chunk: int = 32, engine: str = "scan",
            merge_every: int = 1, overlap_merge: bool = False,
            merge_compression=None, merge_state: dict | None = None,
            merge_plan=None):
        """Run the paper's iterative loop: local partials -> merge -> update.

        ``update_fn(state, merged) -> (state, metrics)`` runs "on the host"
        (replicated).  Returns ``(state, [metrics per step])`` — always
        one history entry per *local* step, whatever the cadence.

        ``engine="scan"`` (default) compiles the loop as chunked
        ``lax.scan`` (see DESIGN in the module docstring);
        ``engine="python"`` is the seed's one-dispatch-per-step loop,
        kept as the parity oracle and benchmark baseline.

        ``merge_plan`` is the canonical way to configure the merge: a
        ``repro.distributed.merge_plan.MergePlan`` composing cadence ×
        overlap × compression × outer optimizer (SlowMo, adaptive
        cadence).  The legacy kwargs are thin constructors for it:
        ``merge_every=k`` ≡ ``MergePlan(cadence=k)``,
        ``overlap_merge=True`` ≡ ``MergePlan(overlap=True)``,
        ``merge_compression=cfg`` ≡ ``MergePlan(compression=cfg)`` —
        pass one spelling or the other, not both.  ``merge_plan=None``
        with the legacy kwargs at their defaults runs the exact engine
        in this file (bit-exact with the pre-plan releases).
        ``merge_plan="auto"`` hands plan selection to the self-tuning
        controller (``repro.tuning``): a roofline cost model ranks
        candidate (cadence, wire-format) tuples, measured round times
        refine the choice, and the decisions land in
        ``merge_state["tuning_trace"]``.

        ``merge_every=k`` runs ``k`` vDPU-local update steps between
        hierarchical state merges (DESIGN — merge cadence).  ``k=1``
        (default) is the PR 1 merge-per-step engine, bit-exact.  At
        ``k > 1`` the scanned unit is one merge round, so ``scan_chunk``
        counts rounds; state pytrees must be float (the merge averages
        them).

        Non-default plans (overlap, compression, SlowMo outer momentum,
        adaptive cadence) are driven by
        ``distributed.merge_plan.run_fit`` — see that module's DESIGN
        notes for the pipeline, carry layouts and the error-feedback /
        momentum buffers.  When a ``merge_state`` dict is passed, those
        buffers are read from it at entry (``"error"``, ``"momentum"``)
        and written back at exit so they continue across ``fit`` calls
        and Trainer restarts.

        Example — GD toward the global mean; cadence 4 pays 1/4 the
        merges and still converges (local means average to the global
        one):

        >>> import jax.numpy as jnp
        >>> from repro.core.pim import make_cpu_grid
        >>> grid = make_cpu_grid(4)
        >>> data, n = grid.shard_rows(jnp.arange(8.0)[:, None])
        >>> def local_fn(w, sl):
        ...     return {"g": jnp.sum((w - sl["X"]) * sl["w"][:, None],
        ...                          axis=0)}
        >>> def update_fn(w, merged):
        ...     return w - 0.1 * merged["g"] / n, {"g0": merged["g"][0]}
        >>> w, hist = grid.fit(init_state=jnp.zeros((1,)),
        ...                    local_fn=local_fn, update_fn=update_fn,
        ...                    data=data, steps=40)
        >>> len(hist)
        40
        >>> bool(jnp.abs(w[0] - 3.5) < 0.1)
        True
        >>> w4, hist4 = grid.fit(init_state=jnp.zeros((1,)),
        ...                      local_fn=local_fn, update_fn=update_fn,
        ...                      data=data, steps=40, merge_every=4)
        >>> len(hist4)
        40
        >>> bool(jnp.abs(w4[0] - 3.5) < 0.2)
        True
        """
        from repro.distributed import merge_plan as mp

        if engine not in ("python", "scan"):
            raise ValueError(f"unknown engine {engine!r}")
        if scan_chunk < 1:
            raise ValueError(f"scan_chunk must be >= 1, got {scan_chunk}")
        if merge_every < 1:
            raise ValueError(
                f"merge_every must be >= 1, got {merge_every}")

        plan = mp.MergePlan.resolve(
            merge_plan, merge_every=merge_every,
            overlap_merge=overlap_merge,
            merge_compression=merge_compression)

        # out-of-core streaming: when ``data`` is a PartitionRotation
        # (data.pipeline), the rotation driver swaps resident
        # partitions between merge rounds and re-enters fit() per
        # window — so every engine path below (and the armed-faults
        # hook) applies unchanged within a window
        if getattr(data, "is_streaming_rotation", False):
            from repro.data import pipeline as _pipeline

            return _pipeline.run_streaming_fit(
                self, data, init_state=init_state, local_fn=local_fn,
                update_fn=update_fn, steps=steps, plan=plan,
                merge_state=merge_state, callback=callback,
                scan_chunk=scan_chunk, engine=engine)

        # fault-injection hook (repro.resilience): when a FaultPlan is
        # armed, non-controller fits run under the resilient driver —
        # survivor-weighted merges, deterministic injection, rollback.
        # Unarmed cost: this one None check.
        ctx = _faults.armed_context()
        if ctx is not None and not (plan.adaptive or plan.auto):
            from repro.resilience import runtime as _resilient

            fplan, recovery, ckpt, ckpt_every = ctx
            state, history, _report = _resilient.drive_fit(
                self, init_state=init_state, local_fn=local_fn,
                update_fn=update_fn, data=data, steps=steps,
                plan=plan, fault_plan=fplan, recovery=recovery,
                ckpt=ckpt, ckpt_every_rounds=ckpt_every,
                scan_chunk=scan_chunk, callback=callback,
                merge_state=merge_state)
            return state, history

        if not plan.is_exact_default:
            return mp.run_fit(
                self, plan, init_state=init_state, local_fn=local_fn,
                update_fn=update_fn, data=data, steps=steps,
                callback=callback, scan_chunk=scan_chunk, engine=engine,
                merge_state=merge_state)

        merge_every = plan.cadence

        if engine == "python":
            if merge_every == 1:
                @jax.jit
                def one_step(state, data):
                    merged = self.map_reduce(local_fn, state, data)
                    return update_fn(state, merged)

                history = []
                state = init_state
                for step in range(steps):
                    state, metrics = one_step(state, data)
                    history.append(metrics)
                    if callback is not None:
                        callback(step, state, metrics)
                return state, history

            # cadence > 1: one dispatch per merge round (the cadence
            # analogue of the seed loop — parity oracle for the scanned
            # rounds below).  A round of one step is a merge-per-step
            # round, so it uses the merged body — same semantics the
            # scan path's remainder runner compiles.
            round_fns: dict = {}
            history = []
            state = init_state
            done = 0
            while done < steps:
                k = min(merge_every, steps - done)
                fn = round_fns.get(k)
                if fn is None:
                    if k == 1:
                        def fn(st, d):
                            merged = self.map_reduce(local_fn, st, d)
                            return update_fn(st, merged)
                        fn = jax.jit(fn)
                    else:
                        fn = jax.jit(
                            lambda st, d, _k=k: mp.cadence_round(
                                self, local_fn, update_fn, _k, st, d))
                    round_fns[k] = fn
                state, stacked = fn(state, data)
                for j in range(k):
                    metrics = jax.tree.map(
                        lambda x, j=j: x[j] if k > 1 else x, stacked)
                    history.append(metrics)
                    if callback is not None:
                        callback(done + j, state, metrics)
                done += k
            return state, history

        history = []
        state = init_state
        if steps > 0 and mp.donating_backend():
            # the runner donates its carry argument — copy so the
            # caller's init_state buffers survive the first chunk
            state = jax.tree.map(
                lambda x: x.copy() if isinstance(x, jax.Array) else x,
                state)

        if merge_every == 1:
            runner = self.make_runner(local_fn, update_fn)
            done = 0
            while done < steps:
                length = min(scan_chunk, steps - done)
                state, stacked = runner(state, data, length=length)
                for i in range(length):
                    metrics = jax.tree.map(lambda x, i=i: x[i], stacked)
                    history.append(metrics)
                    if callback is not None:
                        callback(done + i, state, metrics)
                done += length
            return state, history

        # cadence > 1: scan over merge rounds; metric leaves come back
        # (length, k, ...) and flatten to one history entry per local
        # step.  The steps % k remainder runs as one short round whose
        # runner caches under its own merge_every key.
        rounds, rem = divmod(steps, merge_every)
        runner = self.make_runner(local_fn, update_fn,
                                  merge_every=merge_every)
        done_rounds = 0
        while done_rounds < rounds:
            length = min(scan_chunk, rounds - done_rounds)
            state, stacked = runner(state, data, length=length)
            for r in range(length):
                for j in range(merge_every):
                    metrics = jax.tree.map(
                        lambda x, r=r, j=j: x[r, j], stacked)
                    history.append(metrics)
                    if callback is not None:
                        callback((done_rounds + r) * merge_every + j,
                                 state, metrics)
            done_rounds += length
        if rem:
            # rem == 1 is served by the cadence-1 (merge-per-step)
            # runner, whose metric leaves are (1, ...) not (1, rem, ...)
            rem_runner = self.make_runner(local_fn, update_fn,
                                          merge_every=rem)
            state, stacked = rem_runner(state, data, length=1)
            for j in range(rem):
                metrics = jax.tree.map(
                    lambda x, j=j: x[0, j] if rem > 1 else x[0], stacked)
                history.append(metrics)
                if callback is not None:
                    callback(rounds * merge_every + j, state, metrics)
        return state, history


def make_cpu_grid(n_vdpus: int = 64) -> PimGrid:
    """Single-device grid used by tests/benchmarks on the CPU container."""
    return PimGrid(n_vdpus=n_vdpus, mesh=None)


def make_mesh_grid(n_vdpus: int = 64, *, pods: int = 1,
                   data: int | None = None,
                   mesh: Mesh | None = None) -> PimGrid:
    """A grid whose vDPU axis is sharded over a real device mesh.

    The mesh carries the engine's two-level hierarchy as axes
    ``("pod", "data")`` — ``pod`` is the slow compressible "host hop"
    (reduced last; on TPU multi-pod this is DCN), ``data`` the fast ICI
    axis — built over the local devices by ``launch.mesh.make_pim_mesh``
    unless an explicit ``mesh`` (with those axis names) is passed.
    ``n_vdpus`` must be divisible by the device count: each device runs
    its share of vDPUs as vmap lanes, exactly like the single-device
    grid, and merges cross the mesh as hierarchical psums.

    Works at any device count — on 1 device the mesh is ``(1, 1)`` and
    the engine runs the same ``shard_map`` path the 8-device CI job
    exercises:

    >>> import jax.numpy as jnp
    >>> from repro.core.pim import make_mesh_grid
    >>> grid = make_mesh_grid(8)
    >>> data, n = grid.shard_rows(jnp.arange(16.0)[:, None])
    >>> out = grid.map_reduce(
    ...     lambda w, sl: {"s": jnp.sum(sl["X"] * sl["w"][:, None])},
    ...     None, data)
    >>> float(out["s"])
    120.0
    """
    if mesh is None:
        from repro.launch.mesh import make_pim_mesh
        mesh = make_pim_mesh(pods, data)
    return PimGrid(n_vdpus=n_vdpus, mesh=mesh,
                   data_axes=tuple(mesh.axis_names))
