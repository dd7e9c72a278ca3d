"""MergePlan — the merge side of the PIM engine as a composable object.

The paper's central lesson is that PIM training throughput is governed
by how often and how cheaply vDPU-local state crosses the merge
hierarchy (insights I5/I1), and PIM-Opt (arXiv 2404.07164) shows the
*algorithmic* side of that axis — local-SGD cadence, outer momentum,
communication sparsification — matters as much as the wire format.
This module owns all of it.  A merge plan composes four orthogonal
choices:

    MergePlan(cadence   = how many vDPU-local steps between merges,
              overlap   = double-buffer the merge behind the next
                          round's compute (one round of staleness),
              compression = what the slow "host hop" carries
                          (CompressionConfig: int8 EF wire and/or
                          top-k sparsification; None = exact),
              outer     = what happens AT the merge boundary
                          (an OuterOptimizer))

``PimGrid.fit(merge_plan=...)`` is the canonical entry point; the
legacy ``merge_every=`` / ``overlap_merge=`` / ``merge_compression=``
kwargs are thin constructors for the equivalent plan.  A default plan
(``MergePlan()`` or ``merge_plan=None``) routes through the untouched
bit-exact engine in ``core/pim.py``.

DESIGN — outer optimizers (the merge-boundary commit)
-----------------------------------------------------

Every merge round produces a *proposed delta*: ``avg(lane states) −
phase start`` at cadence k, ``update_fn(state, merged) − state`` at
cadence 1.  The ``OuterOptimizer`` decides how that delta commits:

* ``AverageCommit`` — ``state += delta`` (bit-exact with the pre-plan
  engine; at cadence 1 the commit is literally ``update_fn``'s output).
* ``SlowMo`` — slow momentum at merge boundaries (SlowMo,
  arXiv 1910.00643; the PIM-Opt outer loop): the negated delta is a
  pseudo-gradient fed to a momentum step, ``m ← β·m − delta``,
  ``state ← state − α·m``.  ``β=0, α=1`` recovers ``AverageCommit``
  up to float association.  The momentum buffer rides in the scan
  carry next to the error-feedback buffer, continues across ``fit``
  calls via ``merge_state["momentum"]``, and is Trainer-checkpointed.
* ``AdaptiveCadence`` — a *host-side controller*, not a new update
  rule (its commit is the average): it watches the norm of successive
  merged deltas and grows the cadence ``k`` geometrically once they
  stabilize — pay merges only while they still change the trajectory.
  Rounds dispatch one at a time (the controller sits on the host, like
  the paper's CPU), always on the state wire so the EF buffer never
  changes shape, and each distinct ``k`` compiles once: revisiting a
  cadence hits the grid's runner cache.  Since the tuning extraction
  this is a thin preset over ``repro.tuning.PlanController``; the
  string spelling ``merge_plan="auto"`` (the ``tuning.AutoTune``
  preset) extends the same controller to also choose the wire format
  from a roofline cost-model prior refined by measured round times.

DESIGN — the overlapped + compressed merge pipeline
---------------------------------------------------

Cadence amortises the merge; overlap hides it; compression shrinks it
(paper I5: the merge is tolerable *when overlapped with compute*; I1:
fixed point is what the wire should carry).

* ``overlap=True`` — **double-buffered chunk dispatch**.  The scan
  carry grows a second buffer: the previous round's *un-reduced*
  partials.  Each scan iteration emits the hierarchical reduction of
  round ``i`` (reading the pending buffer) alongside round ``i+1``'s
  local compute (reading the state) — data-independent by
  construction, which is the precondition for XLA's latency-hiding
  scheduler to run the merge as async collectives behind the dots
  (``distributed.overlap.double_buffered_body`` is the combinator;
  ``launch.dryrun_pim --overlap-merge`` verifies the schedule in the
  compiled HLO).  The price is one round of staleness: the merge
  applied at round ``i`` was computed at round ``i-1``'s state.  At
  cadence 1 a prologue computes the first partials (so the first
  update is exact) and the final fresh partials are discarded; at
  cadence ``k`` the merge is a *delayed-delta* outer step — pending
  carries ``(phase-end lanes, phase-start anchor)`` and the commit
  applies ``avg(lanes) − start`` to the live anchor through the outer
  optimizer (a replacement commit would split the scan into two
  interleaved half-rate chains; the delta commit keeps one chain
  advancing every round).  The pipeline primes with one real
  uncommitted phase and drains by committing the last pending delta.
  Lane sums on this path are emitted as ones-vector contractions
  (``distributed.collectives.lane_sum``) — the reduction runs on the
  MXU like the kernels' one-hot matmuls.  Metric merges stay on the
  eager path (scalar-sized; keeps history aligned to steps).
* ``compression=CompressionConfig(bits=8)`` — **compressed merges**.
  Float leaves crossing the host hop are fixed-point quantized with
  error feedback: the quantization residual of round ``i`` is added to
  round ``i+1``'s input, keeping compressed SGD within O(1) of exact.
  ``CompressionConfig(top_k_frac=f)`` additionally keeps only the
  largest-|.| fraction ``f`` of each float leaf per round (same EF
  machinery — dropped entries become next round's residual; indices
  cross the wire exact, values at ``bits`` or raw when ``bits=None``).
  Integer-dtype leaves (counts, histograms) always cross exact.  The
  error buffer is part of the scan carry and must survive across
  chunks, ``fit`` calls and Trainer restarts: ``fit`` reads/writes it
  via the ``merge_state`` holder and the Trainer checkpoints it next
  to the model state.

Carry layouts (``mom`` is the outer-optimizer buffer, ``()`` for
average commits; ``ef`` is ``None`` without compression):

    non-overlap: (state, ef, mom)
    overlap:     (state, pending, ef, mom)

Example — a SlowMo plan at cadence 4 converges on the same problem the
default plan solves:

>>> import jax.numpy as jnp
>>> from repro.core.pim import make_cpu_grid
>>> from repro.distributed.merge_plan import MergePlan, SlowMo
>>> grid = make_cpu_grid(4)
>>> data, n = grid.shard_rows(jnp.arange(8.0)[:, None])
>>> def local_fn(w, sl):
...     return {"g": jnp.sum((w - sl["X"]) * sl["w"][:, None], axis=0)}
>>> def update_fn(w, merged):
...     return w - 0.1 * merged["g"] / n, {"g0": merged["g"][0]}
>>> plan = MergePlan(cadence=4, outer=SlowMo(beta=0.5))
>>> w, hist = grid.fit(init_state=jnp.zeros((1,)), local_fn=local_fn,
...                    update_fn=update_fn, data=data, steps=40,
...                    merge_plan=plan)
>>> len(hist)
40
>>> bool(jnp.abs(w[0] - 3.5) < 0.2)
True
"""

from __future__ import annotations

import dataclasses
import warnings
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.distributed import collectives as coll
from repro.distributed import compression as comp
from repro.distributed.compression import CompressionConfig
from repro.distributed.overlap import double_buffered_body


_FIT_CACHE_MAX = 64


class MergeFallbackWarning(UserWarning):
    """An algorithm accepted a merge-plan knob it cannot honour and fell
    back to exact merge-every-step semantics (e.g. dtree's discrete
    split commits cannot be averaged at cadence > 1)."""


def warn_fallback(algo: str, knobs: str, reason: str) -> None:
    """Emit the structured fallback warning (once per ``fit`` call —
    callers invoke this at most once per training entry)."""
    warnings.warn(
        f"{algo}: {knobs} requested but not honoured — {reason}; "
        f"running exact merge-per-step semantics instead",
        MergeFallbackWarning, stacklevel=3)


# -- caching helpers (shared with PimGrid.make_runner) -----------------


def donating_backend() -> bool:
    """Whether jit buffer donation is real here.  Single source of truth
    for the donate_argnums decision and fit's defensive init_state copy —
    the two must stay in lockstep or callers hit use-after-donate."""
    return jax.default_backend() in ("gpu", "tpu")


def fn_signature(fn) -> tuple:
    """Cache key for a step function: code identity + closure contents.

    ``train_*`` re-creates its closures on every call, so keying the
    compile cache on function *identity* would never hit.  Two closures
    with the same code object and the same captured values (primitives by
    value, everything else by object identity) trace to the same jaxpr,
    so they can share a compiled runner.  Callers must keep the closure
    alive while the key is in use (the cache stores the functions next to
    the runner) so ``id()`` keys cannot be recycled.

    Containers (tuples, string-keyed dicts) and *hashable* frozen
    dataclasses key recursively / by value — the Workload layer
    (``core.mlalgos.api``) captures the estimator instance and its
    trace-time constants in default args, and two equal estimator
    configurations must share a runner while two different
    hyperparameter sets must never collide.  Anything unhashable
    (arrays, live objects) still keys by identity.
    """
    code = getattr(fn, "__code__", None)
    if code is None:
        return (fn,)

    def value_key(v):
        if isinstance(v, (int, float, bool, str, bytes, type(None))):
            return v
        if isinstance(v, tuple):
            return tuple(value_key(x) for x in v)
        if isinstance(v, dict):
            try:
                items = sorted(v.items(), key=lambda kv: kv[0])
            except TypeError:
                return id(v)
            return ("dict",) + tuple((k, value_key(x)) for k, x in items)
        if dataclasses.is_dataclass(v) and not isinstance(v, type):
            try:
                hash(v)
            except TypeError:
                return id(v)
            return v
        return id(v)

    cells = ()
    if fn.__closure__:
        cells = tuple(value_key(c.cell_contents) for c in fn.__closure__)
    # default args are trace-time constants too (the `lr=lr` binding
    # pattern) — they must distinguish keys exactly like closure cells
    defaults = tuple(value_key(v) for v in (fn.__defaults__ or ()))
    kwdefaults = tuple(sorted(
        (k, value_key(v)) for k, v in (fn.__kwdefaults__ or {}).items()))
    return (code, cells, defaults, kwdefaults)


def cache_get(grid, key):
    """LRU lookup in the grid's runner cache.  The touch matters:
    never-repeating keys (quantized paths capture fresh scale arrays per
    call) must not push the long-lived hot runners out of the window."""
    entry = grid._fit_cache.get(key)
    if entry is None:
        return None
    grid._fit_cache[key] = grid._fit_cache.pop(key)
    return entry[0]


def cache_put(grid, key, runners, local_fn, update_fn):
    """Insert with bounded eviction.  The functions ride along so the
    id()-based cells in the key stay alive (no id recycling while the
    entry exists)."""
    while len(grid._fit_cache) >= _FIT_CACHE_MAX:
        grid._fit_cache.pop(next(iter(grid._fit_cache)))
    grid._fit_cache[key] = (runners, local_fn, update_fn)


# -- outer optimizers --------------------------------------------------


@dataclasses.dataclass(frozen=True)
class OuterOptimizer:
    """What happens at a merge boundary: ``commit`` folds the merged
    delta into the anchor state, optionally through a buffer that rides
    in the scan carry (``init`` builds it; ``()`` means stateless).

    ``plain_commit`` marks optimizers whose commit is exactly
    ``anchor + delta`` with no buffer — executors keep the engine's
    original (bit-exact) commit expressions for those and never call
    ``commit``.  A subclass that overrides ``commit`` is therefore
    automatically marked ``plain_commit = False`` unless it says
    otherwise — a custom commit that silently never ran would be a
    correctness trap.
    """

    plain_commit = True

    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        if "commit" in cls.__dict__ and "plain_commit" not in cls.__dict__:
            cls.plain_commit = False

    def init(self, state: Any) -> Any:
        return ()

    def commit(self, anchor: Any, delta: Any, buf: Any):
        return jax.tree.map(lambda a, d: a + d, anchor, delta), buf


@dataclasses.dataclass(frozen=True)
class AverageCommit(OuterOptimizer):
    """The pre-plan semantics: commit the averaged state / updated
    state as-is.  Bit-exact with the PR 3 engine by construction."""


@dataclasses.dataclass(frozen=True)
class SlowMo(OuterOptimizer):
    """Slow momentum at merge boundaries (SlowMo, arXiv 1910.00643).

    The merge delta is treated as a negated pseudo-gradient for a
    momentum step with slow learning rate ``outer_lr`` and momentum
    ``beta`` (see ``repro.optim.optimizers.slow_momentum``); the
    momentum buffer is float32, shaped like the state, and congruent
    across cadences (it lives at merge-round granularity).
    """

    beta: float = 0.5
    outer_lr: float = 1.0

    plain_commit = False

    def _opt(self):
        from repro.optim.optimizers import slow_momentum
        return slow_momentum(self.outer_lr, beta=self.beta)

    def init(self, state: Any) -> Any:
        return self._opt().init(state)

    def commit(self, anchor: Any, delta: Any, buf: Any):
        pseudo_grad = jax.tree.map(lambda d: -d, delta)
        return self._opt().update(pseudo_grad, buf, anchor)


@dataclasses.dataclass(frozen=True)
class Nesterov(OuterOptimizer):
    """Nesterov-style outer momentum at merge boundaries — the
    *lookahead* variant of :class:`SlowMo`'s heavy-ball outer step
    (ROADMAP "Next": Nesterov / FedAdam-style outer optimizers; the
    FedNAG shape of the PIM-Opt outer loop).

    The merge delta is the negated pseudo-gradient ``g = −delta``; the
    commit is Nesterov momentum with slow rate ``outer_lr`` and
    momentum ``beta`` (``optim.optimizers.nesterov``):

        m ← β·m + g,   state ← state − α·(g + β·m)

    ``β=0, α=1`` recovers the plain average.  The buffer rides the
    scan carry exactly like SlowMo's (``merge_state["momentum"]``,
    Trainer-checkpointed in the v2 layout).
    """

    beta: float = 0.5
    outer_lr: float = 1.0

    plain_commit = False

    def _opt(self):
        from repro.optim.optimizers import nesterov
        return nesterov(self.outer_lr, beta=self.beta)

    def init(self, state: Any) -> Any:
        return self._opt().init(state)

    def commit(self, anchor: Any, delta: Any, buf: Any):
        pseudo_grad = jax.tree.map(lambda d: -d, delta)
        return self._opt().update(pseudo_grad, buf, anchor)


@dataclasses.dataclass(frozen=True)
class AdaptiveCadence(OuterOptimizer):
    """Host-side cadence adaptation: start at the plan's ``cadence``
    and grow ``k`` by ``growth`` (up to ``k_max``) once the norms of
    ``patience + 1`` successive merged deltas agree to within
    ``stable_ratio`` relative change.  The commit itself is the plain
    average.

    This is now a thin *preset* over the unified
    ``repro.tuning.PlanController`` (which folded the old private
    cadence controller in): the wire format stays pinned to the plan's
    ``compression`` and only the cadence moves.  With ``shrink=True``
    a delta-norm spike past ``spike_ratio`` × the previous norm halves
    ``k`` toward ``k_min`` — the trajectory is moving again, merge more
    often; the default never shrinks, exactly the legacy behaviour.
    For controller-chosen compression too, use ``merge_plan="auto"``
    (the ``tuning.AutoTune`` preset)."""

    k_max: int = 16
    growth: int = 2
    stable_ratio: float = 0.5
    patience: int = 2
    shrink: bool = False
    spike_ratio: float = 4.0
    k_min: int = 1

    # the controlled-fit driver reads these; AdaptiveCadence pins the
    # wire format so there is nothing to explore or hold for
    explore_rounds = 0
    min_steps_to_explore = 0
    hold_rounds = 1

    def __post_init__(self):
        if self.k_max < 1 or self.growth < 2:
            raise ValueError(
                f"AdaptiveCadence needs k_max >= 1 and growth >= 2, got "
                f"k_max={self.k_max} growth={self.growth}")
        if not 1 <= self.k_min <= self.k_max:
            raise ValueError(
                f"AdaptiveCadence needs 1 <= k_min <= k_max, got "
                f"k_min={self.k_min} k_max={self.k_max}")
        if self.spike_ratio <= 1.0:
            raise ValueError(
                f"AdaptiveCadence.spike_ratio must be > 1, got "
                f"{self.spike_ratio}")


# -- the plan ----------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MergePlan:
    """cadence × overlap × compression × outer — see the module
    docstring.  Hashable (participates in runner cache keys)."""

    cadence: int = 1
    overlap: bool = False
    compression: Optional[CompressionConfig] = None
    outer: OuterOptimizer = AverageCommit()

    def __post_init__(self):
        if self.cadence < 1:
            raise ValueError(
                f"MergePlan.cadence must be >= 1, got {self.cadence}")
        if not isinstance(self.outer, OuterOptimizer):
            raise ValueError(
                f"MergePlan.outer must be an OuterOptimizer, got "
                f"{self.outer!r}")
        if (self.adaptive or self.auto) and self.overlap:
            raise ValueError(
                "controller-driven plans (AdaptiveCadence / auto) "
                "cannot be combined with overlap=True: the controller "
                "re-decides k per round on the host, the overlap "
                "pipeline's pending buffer is shaped per-k")

    @classmethod
    def from_legacy(cls, *, merge_every: int = 1,
                    overlap_merge: bool = False,
                    merge_compression: Optional[CompressionConfig] = None
                    ) -> "MergePlan":
        """The legacy ``fit`` kwargs as a plan (thin constructor)."""
        return cls(cadence=merge_every, overlap=bool(overlap_merge),
                   compression=merge_compression)

    @classmethod
    def resolve(cls, merge_plan: "MergePlan | str | None", *,
                merge_every: int = 1, overlap_merge: bool = False,
                merge_compression: Optional[CompressionConfig] = None
                ) -> "MergePlan":
        """The one resolution rule for the ``fit`` spellings: a given
        plan wins but must not be mixed with non-default legacy kwargs;
        otherwise the kwargs build the plan.  The string ``"auto"``
        resolves to the self-tuning preset (``repro.tuning.AutoTune``:
        the controller picks cadence and wire format from a roofline
        prior plus measured round times).  Every entry point accepting
        these spellings (``PimGrid.fit``, ``api.fit``, ``train_dtree``)
        funnels through here so the rule cannot drift."""
        if isinstance(merge_plan, str):
            if merge_plan != "auto":
                raise ValueError(
                    f"unknown merge_plan spelling {merge_plan!r}: the "
                    f"only string form is 'auto' (or pass a MergePlan)")
            from repro.tuning import auto_plan
            merge_plan = auto_plan()
        if merge_plan is not None:
            if merge_every != 1 or overlap_merge or \
                    merge_compression is not None:
                raise ValueError(
                    "pass either merge_plan= or the legacy kwargs "
                    "(merge_every / overlap_merge / merge_compression), "
                    "not both")
            return merge_plan
        return cls.from_legacy(merge_every=merge_every,
                               overlap_merge=overlap_merge,
                               merge_compression=merge_compression)

    @property
    def adaptive(self) -> bool:
        return isinstance(self.outer, AdaptiveCadence)

    @property
    def auto(self) -> bool:
        """Whether the outer is the self-tuning ``AutoTune`` preset
        (duck-typed so this module never imports ``repro.tuning`` at
        module scope)."""
        return bool(getattr(self.outer, "is_auto", False))

    @property
    def is_exact_default(self) -> bool:
        """Plans served by the untouched bit-exact engine in core/pim
        (any cadence, but no overlap / compression / outer state)."""
        return (not self.overlap and self.compression is None
                and type(self.outer) is AverageCommit)

    def describe(self) -> str:
        parts = [f"cadence={self.cadence}"]
        if self.overlap:
            parts.append("overlap")
        if self.compression is not None:
            parts.append(f"compression={self.compression!r}")
        if type(self.outer) is not AverageCommit:
            parts.append(f"outer={self.outer!r}")
        return "MergePlan(" + ", ".join(parts) + ")"


# -- wire layout -------------------------------------------------------


def hop_size(grid) -> int:
    """Participants on the compressible slow hop (= size of
    ``data_axes[0]``; 1 without a mesh).  The error-feedback buffer
    carries one slice per participant on its leading axis."""
    if grid.mesh is None:
        return 1
    return int(grid.mesh.shape[grid.data_axes[0]])


def wire_spec(grid, local_fn: Callable, update_fn: Callable,
              state: Any, data: Any, *, merge_every: int = 1):
    """ShapeDtypeStruct tree of what crosses the host hop per merge
    round: the partial-statistics tree at cadence 1, the state tree at
    cadence ``k > 1`` (metrics merge eagerly/exactly and are not part
    of the compressible wire).  Used to size error-feedback buffers and
    to compute ``merge_bytes`` analytically."""
    if merge_every == 1:
        sl = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(tuple(x.shape)[1:], x.dtype),
            data)
        return jax.eval_shape(local_fn, state, sl)
    return jax.eval_shape(lambda s: s, state)


def init_merge_error(grid, wire: Any) -> Any:
    """Zero error-feedback buffer for a wire tree: one slice per
    slow-hop participant on the leading axis.  Sharded over the slow
    axis when a mesh is present."""
    hop = hop_size(grid)

    def z(x):
        return jnp.zeros((hop,) + tuple(x.shape), x.dtype)

    ef = jax.tree.map(z, wire)
    if grid.mesh is not None:
        spec = NamedSharding(grid.mesh, P(grid.data_axes[0]))
        ef = jax.tree.map(lambda x: jax.device_put(x, spec), ef)
    return ef


def _ef_spec(grid):
    """shard_map PartitionSpec for an error-feedback leaf (leading hop
    axis over the slow mesh axis)."""
    return P(grid.data_axes[0])


# -- the exact cadence round (the default engine's k-step body) --------


def cadence_round(grid, local_fn: Callable, update_fn: Callable,
                  k: int, state: Any, data: Any):
    """One exact merge round at cadence ``k``: every vDPU runs ``k``
    local update steps on its own copy of ``state`` (no cross-shard
    traffic), then the per-vDPU states and per-step metrics are
    averaged hierarchically (vmap-lane sum -> ICI psum -> pod psum,
    the same tree as ``PimGrid.map_reduce``).

    Local partials are pre-scaled by ``n_vdpus`` so ``update_fn``'s
    global normalisation sees shard statistics at dataset magnitude
    (the local-SGD view — see the merge-cadence DESIGN note in
    ``core.pim``).

    Returns ``(avg_state, metrics)`` with metric leaves of shape
    ``(k, ...)`` — one entry per local step, averaged over vDPUs.
    This is the bit-exact default-plan body; the plan runners above
    reuse its math through ``pipeline_fns``.
    """
    scale = float(grid.n_vdpus)

    def lanes(state, data):
        def per_vdpu(sl):
            def local_step(st, _):
                part = jax.tree.map(lambda x: x * scale,
                                    local_fn(st, sl))
                return update_fn(st, part)
            return jax.lax.scan(local_step, state, None, length=k)

        states, metrics = jax.vmap(per_vdpu)(data)
        return jax.tree.map(lambda x: jnp.sum(x, axis=0),
                            (states, metrics))

    if grid.mesh is None:
        states, metrics = lanes(state, data)
    else:
        axes = tuple(grid.data_axes)

        def shard_body(state, data):
            part = lanes(state, data)
            for ax in reversed(axes[1:]):
                part = jax.tree.map(
                    lambda x, a=ax: jax.lax.psum(x, a), part)
            return jax.tree.map(
                lambda x: jax.lax.psum(x, axes[0]), part)

        data_specs = jax.tree.map(lambda _: P(axes), data)
        states, metrics = shard_map(
            shard_body, mesh=grid.mesh,
            in_specs=(P(), data_specs), out_specs=P(),
            check_vma=False)(state, data)

    inv = 1.0 / scale
    return (jax.tree.map(lambda x: x * inv, states),
            jax.tree.map(lambda x: x * inv, metrics))


# -- the hierarchical (optionally compressed) reduction ----------------


def merge_pending(grid, pending: Any, ef: Any,
                  compression: Optional[CompressionConfig],
                  scale: float | None):
    """Hierarchically reduce a per-lane tree: MXU-shaped lane sum ->
    fast-axis psums -> (optionally compressed, error-fed) slow hop.

    Must run where the grid's axis names are bound — inside shard_map
    when a mesh is present, plainly at ``mesh=None`` (where the slow
    hop is emulated by an EF quantize round-trip).  ``ef`` is the
    hop-participant-leading error tree (local slice shape ``(1, ...)``
    inside shard_map); returns (merged, ef').
    """
    part = coll.lane_sum(pending, scale=scale)
    if grid.mesh is None:
        if compression is None:
            return part, ef
        sq = jax.tree.map(lambda e: e[0], ef)
        merged, new = comp.ef_compress_tree(part, sq, compression)
        return merged, jax.tree.map(lambda e: e[None], new)

    axes = tuple(grid.data_axes)
    for ax in reversed(axes[1:]):
        part = jax.tree.map(lambda x, a=ax: jax.lax.psum(x, a), part)
    slow = axes[0]
    if compression is None:
        return (jax.tree.map(lambda x: jax.lax.psum(x, slow), part), ef)
    flat, td = jax.tree.flatten(part)
    flat_e = td.flatten_up_to(ef)
    outs, new_e = [], []
    for x, e in zip(flat, flat_e):
        # comp._compressible is the single wire-policy predicate —
        # integer statistics always cross the slow hop exact
        if not comp._compressible(x):
            outs.append(jax.lax.psum(x, slow))
            new_e.append(e)
        elif compression.top_k_frac is not None:
            o, ne = coll.sparse_psum_ef(
                x, e[0], slow, frac=compression.top_k_frac,
                bits=compression.bits,
                error_feedback=compression.error_feedback)
            outs.append(o)
            new_e.append(ne[None])
        elif compression.error_feedback:
            o, ne = coll.quantized_psum_ef(x, e[0], slow,
                                           bits=compression.bits)
            outs.append(o)
            new_e.append(ne[None])
        else:
            outs.append(coll.quantized_psum(x, slow,
                                            bits=compression.bits))
            new_e.append(e)
    return td.unflatten(outs), td.unflatten(new_e)


# -- runner assembly ---------------------------------------------------


def pipeline_fns(grid, local_fn: Callable, update_fn: Callable, *,
                 merge_every: int, compression, state_wire: bool,
                 outer: OuterOptimizer):
    """The mode-specific pieces the plan runners are assembled from:
    ``(merge_fn, compute_fn, commit_fn, prologue)``.

    * cadence 1 (``state_wire=False``): the wire carries the partial
      statistics; ``compute_fn`` is the vmapped ``local_fn``, the
      commit applies ``update_fn`` (metrics derive from the merged
      partials) and threads the proposed delta through ``outer``.
    * cadence k / state wire: the wire carries the per-vDPU end states
      of a k-step local phase; metrics are lane-averaged on the eager
      exact path inside ``compute_fn`` and the commit folds
      ``avg − start`` into the live anchor through ``outer`` (the
      delayed-delta outer step — see the module docstring).

    ``commit_fn(state, merged, mom) -> (state', mom', metrics)``.
    """
    axes = tuple(grid.data_axes) if grid.mesh is not None else None

    def data_specs(data_like):
        return jax.tree.map(lambda _: P(axes), data_like)

    if not state_wire:
        # ---- cadence-1 / partials wire ----
        def compute_local(state, data):
            return jax.vmap(lambda d: local_fn(state, d))(data)

        def compute_fn(state, data):
            if grid.mesh is None:
                return compute_local(state, data), None
            fresh = shard_map(
                compute_local, mesh=grid.mesh,
                in_specs=(P(), data_specs(data)),
                out_specs=P(axes), check_vma=False)(state, data)
            return fresh, None

        def merge_fn(pending, ef):
            if grid.mesh is None:
                return merge_pending(grid, pending, ef, compression,
                                     None)
            espec = jax.tree.map(lambda _: _ef_spec(grid), ef)
            return shard_map(
                lambda p, e: merge_pending(grid, p, e, compression,
                                           None),
                mesh=grid.mesh,
                in_specs=(jax.tree.map(lambda _: P(axes), pending),
                          espec),
                out_specs=(jax.tree.map(lambda _: P(), pending),
                           espec),
                check_vma=False)(pending, ef)

        def commit_fn(state, merged, mom):
            proposed, metrics = update_fn(state, merged)
            if outer.plain_commit:
                # the engine's original commit — bit-exact, no re-
                # association through anchor + (proposed - anchor)
                return proposed, mom, metrics
            delta = jax.tree.map(lambda p, a: p - a, proposed, state)
            new, mom = outer.commit(state, delta, mom)
            return new, mom, metrics

        prologue = compute_fn
        return merge_fn, compute_fn, commit_fn, prologue

    # ---- cadence-k / state wire ----
    #
    # The pipelined cadence round is a *delayed-delta* outer step:
    # pending carries ``(per-lane phase-end states, the anchor the
    # phase started from)``, the merge averages the end states, and
    # the commit applies the averaged delta to the live anchor —
    # ``anchor += avg(lanes) - start`` for the plain average.  A
    # replacement commit (``anchor = avg``) would decouple the overlap
    # scan into two interleaved half-rate chains (the compute reads
    # the pre-commit anchor, so anchors would repeat and every phase
    # would run and merge twice); the delta commit keeps one chain
    # that advances every round, one round stale.
    scale = float(grid.n_vdpus)
    inv = 1.0 / scale

    def phase_local(state, data):
        """k local steps per lane from the shared state; returns
        (per-lane end states, lane-averaged per-step metrics)."""
        def per_vdpu(sl):
            def local_step(st, _):
                part = jax.tree.map(lambda x: x * scale,
                                    local_fn(st, sl))
                return update_fn(st, part)
            return jax.lax.scan(local_step, state, None,
                                length=merge_every)

        states, metrics = jax.vmap(per_vdpu)(data)
        metrics, _ = merge_pending(grid, metrics, None, None, inv)
        return states, metrics

    def compute_fn(state, data):
        if grid.mesh is None:
            lanes, metrics = phase_local(state, data)
        else:
            lanes, metrics = shard_map(
                phase_local, mesh=grid.mesh,
                in_specs=(P(), data_specs(data)),
                out_specs=(P(axes), P()), check_vma=False)(state, data)
        return (lanes, state), metrics

    # top-k sparsification on the state wire rides the *delta*: a
    # state's large entries are simply its large weights (top-k of the
    # state zeroes most of the model every merge — catastrophic), while
    # a k-step local delta is the quantity sparsified local-SGD
    # transmits.  The wire then carries per-lane (end − start) and the
    # merge rebuilds avg = start + avg(delta); the EF buffer stays
    # state-shaped (deltas are congruent with states).
    delta_wire = (compression is not None
                  and compression.top_k_frac is not None)

    def merge_fn(pending, ef):
        lanes, start = pending
        if delta_wire:
            lanes = jax.tree.map(lambda l, s: l - s, lanes, start)
        if grid.mesh is None:
            avg, ef = merge_pending(grid, lanes, ef, compression, inv)
        else:
            espec = jax.tree.map(lambda _: _ef_spec(grid), ef)
            avg, ef = shard_map(
                lambda p, e: merge_pending(grid, p, e, compression,
                                           inv),
                mesh=grid.mesh,
                in_specs=(jax.tree.map(lambda _: P(axes), lanes),
                          espec),
                out_specs=(jax.tree.map(lambda _: P(), lanes),
                           espec),
                check_vma=False)(lanes, ef)
        if delta_wire:
            avg = jax.tree.map(lambda s, d: s + d, start, avg)
        return (avg, start), ef

    def commit_fn(state, merged, mom):
        avg, start = merged
        if outer.plain_commit:
            new = jax.tree.map(lambda s, a, st: s + (a - st),
                               state, avg, start)
            return new, mom, None
        delta = jax.tree.map(lambda a, st: a - st, avg, start)
        new, mom = outer.commit(state, delta, mom)
        return new, mom, None

    def prologue(state, data):
        """Pipeline fill: one real (uncommitted) phase primes the
        pending buffer.  Its lanes are recomputed by round 1's
        ``compute_fn`` (the one-time startup transient: the first
        phase runs twice and its delta commits twice — bounded,
        and the anchor then advances every round)."""
        return compute_fn(state, data)

    return merge_fn, compute_fn, commit_fn, prologue


def pipeline_runners(grid, local_fn: Callable, update_fn: Callable, *,
                     merge_every: int, overlap: bool, compression,
                     state_wire: bool,
                     outer: OuterOptimizer = AverageCommit()) -> dict:
    """Build (and cache on the grid) the jitted pieces for one
    overlap × compression × outer mode: ``runner`` (scanned chunk),
    ``round`` (one dispatch, the python-engine oracle), ``prologue``
    and ``drain`` where the mode needs them.

    Carries are ``(state, ef, mom)`` / ``(state, pending, ef, mom)``;
    ``mom`` is ``()`` for plain commits, so the extra slot costs
    nothing there.
    """
    from repro.kernels import dispatch as _dispatch

    key = (fn_signature(local_fn), fn_signature(update_fn),
           _dispatch.kernels_enabled(), merge_every, overlap,
           compression, state_wire, outer)
    cached = cache_get(grid, key)
    if cached is not None:
        return cached

    merge_fn, compute_fn, commit_fn, prologue = pipeline_fns(
        grid, local_fn, update_fn, merge_every=merge_every,
        compression=compression, state_wire=state_wire, outer=outer)
    donate = (0,) if donating_backend() else ()

    if overlap:
        def body_of(data):
            return double_buffered_body(
                lambda p, e: merge_fn(p, e),
                lambda st: compute_fn(st, data),
                commit_fn)

        @partial(jax.jit, static_argnames=("length",),
                 donate_argnums=donate)
        def runner(carry, data, *, length: int):
            return jax.lax.scan(body_of(data), carry, None,
                                length=length)

        @jax.jit
        def round_fn(carry, data):
            return body_of(data)(carry, None)

        @jax.jit
        def prologue_fn(state, data):
            return prologue(state, data)[0]

        @jax.jit
        def drain_fn(carry):
            state, pending, ef, mom = carry
            merged, ef = merge_fn(pending, ef)
            new_state, mom, _ = commit_fn(state, merged, mom)
            return new_state, ef, mom

        runners = {"runner": runner, "round": round_fn,
                   "prologue": prologue_fn, "drain": drain_fn}
    else:
        def body_of(data):
            def body(carry, _):
                state, ef, mom = carry
                fresh, compute_metrics = compute_fn(state, data)
                merged, ef = merge_fn(fresh, ef)
                new_state, mom, commit_metrics = commit_fn(
                    state, merged, mom)
                metrics = (compute_metrics
                           if compute_metrics is not None
                           else commit_metrics)
                return (new_state, ef, mom), metrics
            return body

        @partial(jax.jit, static_argnames=("length",),
                 donate_argnums=donate)
        def runner(carry, data, *, length: int):
            return jax.lax.scan(body_of(data), carry, None,
                                length=length)

        @jax.jit
        def round_fn(carry, data):
            return body_of(data)(carry, None)

        runners = {"runner": runner, "round": round_fn}

    cache_put(grid, key, runners, local_fn, update_fn)
    return runners


# -- the fit driver ----------------------------------------------------


def _copy_tree(t):
    return jax.tree.map(
        lambda x: x.copy() if isinstance(x, jax.Array) else x, t)


@jax.jit
def _delta_sq_norm(a, b):
    """On-device global squared l2 distance between two state trees —
    the adaptive controller syncs one scalar per round, never the
    state itself (a D2H copy of a large model every round would
    dominate the merge cost the controller exists to amortise)."""
    return sum(
        jnp.sum((x - y).astype(jnp.float32) ** 2)
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def run_fit(grid, plan: MergePlan, *, init_state, local_fn, update_fn,
            data, steps, callback, scan_chunk, engine, merge_state):
    """``fit`` driver for every non-default plan (overlap, compression,
    SlowMo, adaptive cadence, auto).  Mirrors ``PimGrid.fit``'s
    contract: returns ``(state, history)`` with one entry per local
    step; reads and writes the ``merge_state`` holder (``"error"``,
    ``"momentum"``, and — for controller-driven plans —
    ``"cadence_trace"`` / ``"tuning_trace"``)."""
    state = init_state
    history: list = []
    if steps > 0 and donating_backend():
        state = _copy_tree(state)

    # Controller-driven plans reach run_fit even when a FaultPlan is
    # armed (the resilient driver only covers static plans): make the
    # gap loud instead of silently skipping injection.
    from repro.resilience import faults as _faults
    if _faults.active() is not None and (plan.adaptive or plan.auto):
        warnings.warn(
            "a FaultPlan is armed but this fit uses a controller-driven "
            "plan (adaptive/auto); fault injection and recovery only "
            "cover static plans — no faults will be injected",
            MergeFallbackWarning, stacklevel=3)

    compression = plan.compression
    outer = plan.outer

    # state-wire plans (cadence > 1, and every controlled round) carry
    # the state tree on the wire; cadence-1 static plans carry the
    # partials.  Auto plans may compress even though plan.compression
    # is None (the controller chooses), so their EF buffer continues
    # across fit calls through the same merge_state slot.
    ef = None
    if compression is not None or plan.auto:
        ef = merge_state.get("error") if merge_state else None
        if ef is None:
            if compression is not None:
                wire_cadence = 2 if (plan.adaptive or plan.auto) \
                    else plan.cadence
                wire = wire_spec(grid, local_fn, update_fn, state, data,
                                 merge_every=wire_cadence)
                ef = init_merge_error(grid, wire)
            # plan.auto without pinned compression: the controlled-fit
            # driver allocates the shared state-shaped buffer itself
        elif steps > 0 and donating_backend():
            ef = _copy_tree(ef)

    mom: Any = ()
    if not outer.plain_commit:
        mom = merge_state.get("momentum") if merge_state else None
        if mom is None:
            mom = outer.init(state)
        elif steps > 0 and donating_backend():
            mom = _copy_tree(mom)

    if plan.adaptive or plan.auto:
        # the controller extraction: adaptive/auto fits run under
        # repro.tuning's PlanController (AdaptiveCadence is a preset
        # of it — cadence only; AutoTune also selects the wire format
        # from a roofline prior refined by measured round times)
        from repro.tuning.controller import run_controlled_fit

        state, history, ef, ctl = run_controlled_fit(
            grid, plan, state=state, ef=ef, local_fn=local_fn,
            update_fn=update_fn, data=data, steps=steps,
            callback=callback)
        if merge_state is not None:
            if ef is not None:
                merge_state["error"] = ef
            merge_state["cadence_trace"] = list(ctl.cadence_trace)
            merge_state["tuning_trace"] = ctl.trace_dict()
        return state, history

    done = 0

    def emit(metrics, live_state):
        nonlocal done
        history.append(metrics)
        if callback is not None:
            callback(done, live_state, metrics)
        done += 1

    merge_every = plan.cadence
    overlap = plan.overlap
    if merge_every == 1:
        rs = pipeline_runners(
            grid, local_fn, update_fn, merge_every=1, overlap=overlap,
            compression=compression, state_wire=False, outer=outer)
        if overlap:
            carry = (state, rs["prologue"](state, data), ef, mom) \
                if steps > 0 else (state, None, ef, mom)
        else:
            carry = (state, ef, mom)
        if engine == "python":
            for _ in range(steps):
                carry, metrics = rs["round"](carry, data)
                emit(metrics, carry[0])
        else:
            remaining = steps
            while remaining > 0:
                length = min(scan_chunk, remaining)
                carry, stacked = rs["runner"](carry, data,
                                              length=length)
                for i in range(length):
                    emit(jax.tree.map(lambda x, i=i: x[i], stacked),
                         carry[0])
                remaining -= length
        if overlap and steps > 0:
            # cadence-1 drain is a no-op on the state (the final fresh
            # partials are discarded) but the EF/momentum slots live in
            # the carry tail either way
            state, ef, mom = carry[0], carry[2], carry[3]
        else:
            state, ef, mom = carry[0], carry[-2], carry[-1]
    else:
        rounds, rem = divmod(steps, merge_every)
        if rounds:
            rs = pipeline_runners(
                grid, local_fn, update_fn, merge_every=merge_every,
                overlap=overlap, compression=compression,
                state_wire=True, outer=outer)
            if overlap:
                carry = (state, rs["prologue"](state, data), ef, mom)
            else:
                carry = (state, ef, mom)
            if engine == "python":
                for _ in range(rounds):
                    carry, stacked = rs["round"](carry, data)
                    for j in range(merge_every):
                        emit(jax.tree.map(
                            lambda x, j=j: x[j], stacked), carry[0])
            else:
                done_rounds = 0
                while done_rounds < rounds:
                    length = min(scan_chunk, rounds - done_rounds)
                    carry, stacked = rs["runner"](carry, data,
                                                  length=length)
                    for r in range(length):
                        for j in range(merge_every):
                            emit(jax.tree.map(
                                lambda x, r=r, j=j: x[r, j],
                                stacked), carry[0])
                    done_rounds += length
            if overlap:
                # drain: the last phase's states are still pending —
                # commit their delta so no round's work is dropped
                state, ef, mom = rs["drain"](carry)
            else:
                state, ef, mom = carry
        if rem:
            # trailing short round, never overlapped (the pipeline is
            # already drained) and on the state wire whatever ``rem``
            # is, so the EF tree stays congruent with the full rounds
            rs_rem = pipeline_runners(
                grid, local_fn, update_fn, merge_every=rem,
                overlap=False, compression=compression,
                state_wire=True, outer=outer)
            (state, ef, mom), stacked = rs_rem["runner"](
                (state, ef, mom), data, length=1)
            for j in range(rem):
                emit(jax.tree.map(lambda x, j=j: x[0, j], stacked),
                     state)

    if merge_state is not None:
        if compression is not None:
            merge_state["error"] = ef
        if not outer.plain_commit:
            merge_state["momentum"] = mom
    return state, history
