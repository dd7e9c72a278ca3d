"""Workload — the unified estimator API over the PimGrid engine.

Before this layer, each of the paper's algorithms hand-wired its own
``train_*`` entry point, so every new fit axis (cadence, the merge
pipeline, merge plans) had to be threaded through four signatures, the
Trainer, the configs, the dry-run and the benchmarks separately — and
capability gaps (dtree's discrete split commits) were special-cased at
call sites.  A **Workload** packages what is actually per-algorithm:

    init_state(consts)            -> the model pytree
    local_step(consts, state, sl) -> per-vDPU partial statistics
    update(consts, state, merged) -> (state', metrics)   # the host step
    eval(state, X, y)             -> quality metrics
    merge_caps                    -> which merge-plan axes the algorithm
                                     can honour (declared, not special-
                                     cased — see MergeCaps)

plus ``prepare(grid, X, y) -> (data, n, consts)``, the one-time
resident placement (quantize + ``shard_rows``).  Everything else — the
scan engine, merge plans, minibatch sampling, the Trainer, benchmarks,
the dry-run — is generic over the protocol: a new estimator is a
~100-line plugin (``svm.py`` and ``multinomial.py`` are the proof).

``bind`` assembles a :class:`Program`: the closures ``PimGrid.fit``
consumes.  Every ``api.fit`` binds anew (``prepare`` runs in full:
quantization, placement, scales), and the new closures still hit the
engine's signature-keyed compile cache: the workload instance and the
hashable constants (row and feature counts, the sigmoid function) ride
in the closures' default args, which ``merge_plan.fn_signature`` keys
by value, and the array constants (quantization scales) are a runtime
operand of the runner, not a capture.  Two equal estimators on data of
the same shapes share a runner whatever their scales; two different
hyperparameter sets never collide.

DESIGN — the minibatch axis (``fit(batch_size=b)``)
---------------------------------------------------

``batch_size=b`` samples ``b`` of the resident per-vDPU rows each local
step *inside* the compiled scan — a deterministic on-device permutation
schedule with epoch-exact coverage (``core.minibatch``; PIM-Opt's
sampling model).  It is a pure transformation of the engine triple, so
it composes with every ``MergePlan`` axis: cadence-k local SGD runs on
minibatches exactly as in PIM-Opt, overlap and EF compression apply
unchanged.  ``batch_size=None`` (default) bypasses the sampler — the
bit-exact full-batch path.  Stateful outer optimizers (SlowMo,
Nesterov) are refused with ``batch_size``: their momentum would
integrate the sampler's step counter off its integer grid.

Example — the generic entry point, three estimators, one code path:

>>> import jax
>>> from repro.core import datasets, make_cpu_grid
>>> from repro.core.mlalgos import api, LinReg, LinearSVM
>>> X, y, _ = datasets.regression(jax.random.PRNGKey(0), 512, 8)
>>> grid = make_cpu_grid(8)
>>> res = api.fit(LinReg(lr=0.05), grid, X, y, steps=20)
>>> len(res.history)
20
>>> mini = api.fit(LinReg(lr=0.05), grid, X, y, steps=20,
...                batch_size=16, merge_every=4)
>>> mini.state.shape
(8,)
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import minibatch as mb
from repro.core.pim import PimGrid


# ---------------------------------------------------------------------------
# capability flags
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MergeCaps:
    """Which merge-plan / sampling axes a workload can honour.

    Call sites never special-case algorithms: :func:`fit` calls
    :meth:`constrain`, which degrades an unsupported request to the
    exact default *and warns* (the structured
    ``merge_plan.MergeFallbackWarning``), carrying the workload's own
    ``reason``.  The default is "everything" — gradient-style
    estimators whose state is an averageable float pytree.
    """

    cadence: bool = True
    overlap: bool = True
    compression: bool = True
    outer: bool = True
    minibatch: bool = True
    reason: str = ""

    @classmethod
    def exact_only(cls, reason: str) -> "MergeCaps":
        """Merge-every-step, full-batch only (dtree's discrete commits)."""
        return cls(cadence=False, overlap=False, compression=False,
                   outer=False, minibatch=False, reason=reason)

    def constrain(self, name: str, plan, batch_size: Optional[int]):
        """Degrade ``(plan, batch_size)`` to what the workload supports;
        one structured warning lists everything dropped."""
        from repro.distributed import merge_plan as mp

        dropped = []
        changes: dict = {}
        if plan.cadence > 1 and not self.cadence:
            dropped.append(f"merge_every={plan.cadence}")
            changes["cadence"] = 1
        if plan.overlap and not self.overlap:
            dropped.append("overlap_merge")
            changes["overlap"] = False
        if plan.compression is not None and not self.compression:
            dropped.append("merge_compression")
            changes["compression"] = None
        if type(plan.outer) is not mp.AverageCommit and not self.outer:
            dropped.append(f"outer={type(plan.outer).__name__}")
            changes["outer"] = mp.AverageCommit()
        if batch_size is not None and not self.minibatch:
            dropped.append(f"batch_size={batch_size}")
            batch_size = None
        if dropped:
            mp.warn_fallback(name, " + ".join(dropped), self.reason)
            plan = dataclasses.replace(plan, **changes)
        return plan, batch_size


# ---------------------------------------------------------------------------
# the protocol
# ---------------------------------------------------------------------------


class Workload:
    """Base estimator.  Subclasses are frozen dataclasses holding only
    hyperparameters (so equal configurations share compiled runners)
    and implement the five protocol members plus ``prepare``.

    ``consts`` is the dict ``prepare`` returns next to the resident
    data: the constants the step functions read (row count, feature
    count, and the scales of the row format,
    :mod:`~repro.core.mlalgos.fixed_point`).  :meth:`Program.assemble` splits it:
    array values become the runner's ``consts`` operand, everything
    else is captured in the assembled closures and keys the compile
    cache by value, so it must be hashable (primitives, frozen
    dataclasses, functions with a stable identity such as
    ``logreg.make_sigmoid``'s).  ``local_step`` and ``update`` see the
    two parts merged into one dict.  Keys starting with ``"_"`` are
    bind-time-only (read by ``init_state``, excluded from the step
    functions and their cache keys — kmeans' initial centroids live
    there).
    """

    name: str = "workload"
    merge_caps: MergeCaps = MergeCaps()
    # serving capability: False marks host-only forward passes (dtree's
    # numpy searchsorted binning) that the compiled PredictRunner must
    # refuse with a clear error instead of silently dispatching eagerly
    predict_device: bool = True

    # -- protocol ------------------------------------------------------

    def prepare(self, grid: PimGrid, X, y=None):
        """One-time resident placement: returns ``(data, n, consts)``."""
        raise NotImplementedError

    def init_state(self, consts: dict):
        raise NotImplementedError

    def local_step(self, consts: dict, state, sl):
        """Partial statistics over one vDPU's resident slice."""
        raise NotImplementedError

    def update(self, consts: dict, state, merged):
        """Host-side commit of the merged statistics ->
        ``(state', metrics)``."""
        raise NotImplementedError

    def eval(self, state, X, y=None) -> dict:
        raise NotImplementedError

    def predict(self, state, X):
        """The serving-side forward pass: raw predictions for a batch of
        rows — exactly the forward half of :meth:`eval` (same sigmoid /
        softmax variant, same quantized dots), without the metric
        reduction.  fp32 configurations are bit-exact with the
        ``*_predict`` helpers ``eval`` calls; quantized configurations
        put the request rows in the row format
        (:mod:`~repro.core.mlalgos.fixed_point`, ``request``) and run
        ``local_step``'s forward through it.

        Must be *pad-invariant*: appending zero rows to ``X`` never
        changes the predictions of the real rows (the serving runner
        pads requests up to bucket shapes and slices the result).
        """
        raise NotImplementedError(
            f"workload {self.name!r} does not implement predict")

    # -- streaming protocol (out-of-core; opt-in) ----------------------

    def stream_consts(self, stream) -> Optional[dict]:
        """Trace-time constants for an out-of-core fit over a
        :class:`~repro.data.pipeline.StreamingDataset` — the streaming
        analogue of ``prepare``'s consts, derived from one-pass host
        statistics (row count, global quantization scales) because no
        window ever sees the whole dataset.  ``None`` (the default)
        means the workload does not support streaming ingestion;
        :meth:`bind_stream` turns that into a clear error."""
        return None

    def stream_transform(self, consts: dict, X_rows, y_rows):
        """Map a window's raw host rows to the resident representation
        — the streaming analogue of ``prepare``'s pre-shard transform
        (label mapping, fixed-global-scale quantization).  Must be a
        *row-local* map so it commutes with the rotation's gather.
        Returns the ``(X', extra0, ...)`` tuple ``shard_rows`` would
        have been given."""
        return (X_rows,) if y_rows is None else (X_rows, y_rows)

    # -- engine glue ---------------------------------------------------

    def bind(self, grid: PimGrid, X, y=None) -> "Program":
        """Shard the dataset and assemble the engine closures once."""
        with jax.profiler.TraceAnnotation("pim.prepare"):
            data, n, consts = self.prepare(grid, X, y)
        return Program.assemble(self, grid, data, n, consts)

    def bind_stream(self, grid: PimGrid, stream) -> "StreamProgram":
        """Bind an out-of-core :class:`~repro.data.pipeline.
        StreamingDataset`: same closure assembly as :meth:`bind`, but
        the "placement" is a :class:`~repro.data.pipeline.
        PartitionRotation` that materializes resident-sized windows on
        demand (see data.pipeline's DESIGN)."""
        from repro.data.pipeline import PartitionRotation

        consts = self.stream_consts(stream)
        if consts is None:
            raise ValueError(
                f"workload {self.name!r} does not support streaming "
                f"ingestion (stream_consts returned None): its "
                f"prepare-time statistics cannot be derived from "
                f"one-pass host statistics, or nobody has taught it "
                f"to — use the fully-resident path")

        def transform(Xb, yb, _w=self, _c=consts):
            return _w.stream_transform(_c, Xb, yb)

        rotation = PartitionRotation(stream, grid, transform=transform)
        return StreamProgram.assemble(self, grid, rotation,
                                      stream.n_rows, consts)

    def run(self, grid: PimGrid, X, y=None, *, steps: int, plan,
            batch_size: Optional[int], engine: str, scan_chunk: int,
            merge_state: Optional[dict], callback: Optional[Callable],
            sample_seed: int) -> "FitResult":
        """Train-from-raw-arrays entry (already caps-constrained by
        :func:`fit`).  The default is bind + the generic engine loop;
        workloads whose training is not a ``grid.fit`` loop (dtree's
        level-wise host loop) override this."""
        return self.bind(grid, X, y)._run(
            steps=steps, plan=plan, batch_size=batch_size, engine=engine,
            scan_chunk=scan_chunk, merge_state=merge_state,
            callback=callback, sample_seed=sample_seed)


@dataclasses.dataclass
class FitResult:
    """What every workload fit returns: the trained state and one
    metrics entry per local step."""

    state: Any
    history: list
    workload: Workload

    def eval(self, X, y=None) -> dict:
        return self.workload.eval(self.state, X, y)


@dataclasses.dataclass
class Program:
    """A workload bound to a grid and a resident dataset: the stable
    ``(local_fn, update_fn, init_state)`` triple, the array constants
    the triple's functions take as their third argument
    (``array_consts``, ``PimGrid.fit``'s ``consts``) and the placement.
    Every bind of an equal estimator on data of the same shapes gets
    equal compile-cache keys (see the module docstring)."""

    workload: Workload
    grid: PimGrid
    data: Any
    n: int
    consts: dict
    local_fn: Callable
    update_fn: Callable
    state0: Any
    array_consts: dict = dataclasses.field(default_factory=dict)

    @classmethod
    def assemble(cls, workload: Workload, grid: PimGrid, data, n,
                 consts: dict) -> "Program":
        # Hyperparameters and hashable constants ride in the default
        # args, which the compile cache keys by value
        # (merge_plan.fn_signature); array constants are the runner's
        # operand, merged back in at trace time, so new scales never
        # change the key.  Keys starting with "_" are bind-time-only
        # (init_state inputs like kmeans' initial centroids) and stay
        # out of the step functions.
        step_consts = {k: v for k, v in consts.items()
                       if not k.startswith("_")}
        arrays = {k: v for k, v in step_consts.items()
                  if isinstance(v, (jax.Array, np.ndarray))}
        static = {k: v for k, v in step_consts.items() if k not in arrays}

        def local_fn(state, sl, arrays, _w=workload, _c=static):
            return _w.local_step({**_c, **arrays}, state, sl)

        def update_fn(state, merged, arrays, _w=workload, _c=static):
            return _w.update({**_c, **arrays}, state, merged)

        return cls(workload=workload, grid=grid, data=data, n=n,
                   consts=consts, local_fn=local_fn, update_fn=update_fn,
                   state0=workload.init_state(consts),
                   array_consts=arrays)

    @property
    def rows_per_vdpu(self) -> int:
        return int(self.data["w"].shape[1])

    def _triple(self, batch_size: Optional[int], sample_seed: int):
        """The engine triple, minibatch-wrapped when asked.  A wrapper
        keys the compile cache by the signature of the functions it
        wraps, so the wrappers of repeated fits share runners."""
        if batch_size is None:
            return self.local_fn, self.update_fn, self.state0, None
        return mb.minibatch_fns(
            self.local_fn, self.update_fn, self.state0,
            rows_per_vdpu=self.rows_per_vdpu, batch_size=batch_size,
            seed=sample_seed)

    @partial(jax.profiler.annotate_function, name="pim.fit")
    def fit(self, *, steps: int, batch_size: Optional[int] = None,
            engine: str = "scan", scan_chunk: int = 32,
            merge_every: int = 1, overlap_merge: bool = False,
            merge_compression=None, merge_plan=None,
            merge_state: Optional[dict] = None,
            callback: Optional[Callable] = None,
            sample_seed: int = 0) -> FitResult:
        """Train on the bound dataset (same option surface as
        :func:`fit`, minus the binding).  ``merge_plan`` accepts a
        :class:`~repro.distributed.merge_plan.MergePlan`, ``None``
        (exact default), or the string ``"auto"`` — the self-tuning
        controller in ``repro.tuning`` picks cadence and wire format
        and records its decisions in ``merge_state["tuning_trace"]``."""
        from repro.distributed import merge_plan as mp

        plan = mp.MergePlan.resolve(
            merge_plan, merge_every=merge_every,
            overlap_merge=overlap_merge,
            merge_compression=merge_compression)
        plan, batch_size = self.workload.merge_caps.constrain(
            self.workload.name, plan, batch_size)
        return self._run(steps=steps, plan=plan, batch_size=batch_size,
                         engine=engine, scan_chunk=scan_chunk,
                         merge_state=merge_state, callback=callback,
                         sample_seed=sample_seed)

    def _run(self, *, steps, plan, batch_size, engine, scan_chunk,
             merge_state, callback, sample_seed) -> FitResult:
        if batch_size is not None and not plan.outer.plain_commit:
            raise ValueError(
                f"batch_size={batch_size} cannot compose with the "
                f"{type(plan.outer).__name__} outer optimizer: the "
                f"sampler's step counter rides in the merged state and "
                f"a stateful outer commit would integrate it into its "
                f"momentum, breaking the epoch schedule (plain average "
                f"and adaptive-cadence commits keep it exact)")
        local_fn, update_fn, state0, unwrap = self._triple(
            batch_size, sample_seed)
        cb = callback
        if unwrap is not None and callback is not None:
            def cb(step, state, metrics, _u=unwrap, _cb=callback):
                return _cb(step, _u(state), metrics)
        state, history = self.grid.fit(
            init_state=state0, local_fn=local_fn, update_fn=update_fn,
            data=self.data, steps=steps, engine=engine,
            scan_chunk=scan_chunk, merge_plan=plan,
            merge_state=merge_state, callback=cb,
            consts=self.array_consts)
        if unwrap is not None:
            state = unwrap(state)
        return FitResult(state=state, history=history,
                         workload=self.workload)

    def step_fn(self, *, batch_size: Optional[int] = None,
                sample_seed: int = 0):
        """A jitted merge-per-step function for external drivers (the
        fault-tolerant ``Trainer``): ``step(state, batch) -> (state,
        metrics)`` over the resident data (``batch`` is ignored — the
        dataset never moves, insight I4).  Returns ``(step, state0)``;
        with ``batch_size`` the state carries the sampler counter, so
        checkpoint/replay restores the schedule position for free."""
        local_fn, update_fn, state0, _ = self._triple(
            batch_size, sample_seed)
        grid, data, consts = self.grid, self.data, self.array_consts

        @jax.jit
        def step_c(state, batch, consts):
            merged = grid.map_reduce(local_fn, state, data, consts)
            return update_fn(state, merged, consts)

        return partial(step_c, consts=consts), state0

    def round_fn(self, k: int, *, batch_size: Optional[int] = None,
                 sample_seed: int = 0):
        """A jitted exact merge *round* at cadence ``k`` for external
        drivers: ``round(state, batch) -> (state, metrics)`` where each
        call runs ``k`` local steps per vDPU and merges once
        (``merge_plan.cadence_round`` — the bit-exact default-plan
        body).  Metric leaves come back with shape ``(k, ...)``, one
        entry per local step.  Returns ``(round, state0)``; this is how
        ``Trainer.for_program`` honours ``merge_every > 1`` while
        keeping checkpoint/restore at merge boundaries."""
        if k < 1:
            raise ValueError(f"round_fn needs cadence k >= 1, got {k}")
        from repro.distributed import merge_plan as mp

        local_fn, update_fn, state0, _ = self._triple(
            batch_size, sample_seed)
        grid, data, consts = self.grid, self.data, self.array_consts

        @jax.jit
        def round_c(state, batch, consts):
            return mp.cadence_round(grid, local_fn, update_fn, k,
                                    state, data, consts)

        return partial(round_c, consts=consts), state0


@dataclasses.dataclass
class StreamProgram(Program):
    """A workload bound to a grid and an *out-of-core* rotation: the
    same stable triple as :class:`Program`, but ``data`` is a
    :class:`~repro.data.pipeline.PartitionRotation` — ``grid.fit``
    dispatches it to the streaming driver, which swaps resident
    partitions between merge rounds while a prefetcher double-buffers
    the next window's gather + H2D behind compute.

    Everything composes: ``batch_size`` samples *within* the resident
    window (the sampler's ``rows_per_vdpu`` is the window's ``part``
    slots), cadence/overlap/compression run unchanged inside each
    window, and EF/momentum continue across windows through
    ``merge_state``.  Controller plans (``"auto"``/adaptive) are
    refused by the driver — a per-window probe would measure rotation
    noise, not the plan."""

    is_stream_program = True

    @property
    def rows_per_vdpu(self) -> int:
        return self.data.part

    @property
    def stream_tag(self) -> str:
        """Rotation-schedule identity for Trainer checkpoints."""
        return self.data.tag()

    def batch_feed(self, cadence: int = 1):
        """A deterministic ``batch_fn(step)`` over the rotation for the
        fault-tolerant Trainer (window ``step // steps_per_window``,
        prefetched; rebuilt on rollback)."""
        from repro.data.pipeline import RotationFeed

        return RotationFeed(self.data, self.data.steps_per_window(cadence))

    def step_fn(self, *, batch_size: Optional[int] = None,
                sample_seed: int = 0):
        """Like :meth:`Program.step_fn`, but the step consumes the
        ``batch`` argument (the current rotation window) and applies
        the window's unbiased-estimator scale, so the Trainer's
        merge-boundary checkpoints stay exact under rotation."""
        from repro.data.pipeline import make_scaled_local

        local_fn, update_fn, state0, _ = self._triple(
            batch_size, sample_seed)
        slf = (local_fn if self.data.exact_full
               else make_scaled_local(local_fn))
        grid, consts = self.grid, self.array_consts

        @jax.jit
        def step_c(state, batch, consts):
            merged = grid.map_reduce(slf, state, batch, consts)
            return update_fn(state, merged, consts)

        return partial(step_c, consts=consts), state0

    def round_fn(self, k: int, *, batch_size: Optional[int] = None,
                 sample_seed: int = 0):
        if k < 1:
            raise ValueError(f"round_fn needs cadence k >= 1, got {k}")
        from repro.data.pipeline import make_scaled_local
        from repro.distributed import merge_plan as mp

        local_fn, update_fn, state0, _ = self._triple(
            batch_size, sample_seed)
        slf = (local_fn if self.data.exact_full
               else make_scaled_local(local_fn))
        grid, consts = self.grid, self.array_consts

        @jax.jit
        def round_c(state, batch, consts):
            return mp.cadence_round(grid, slf, update_fn, k,
                                    state, batch, consts)

        return partial(round_c, consts=consts), state0


# ---------------------------------------------------------------------------
# the generic entry point
# ---------------------------------------------------------------------------


@partial(jax.profiler.annotate_function, name="pim.fit")
def fit(workload: Workload, grid: PimGrid, X, y=None, *, steps: int,
        batch_size: Optional[int] = None, engine: str = "scan",
        scan_chunk: int = 32, merge_every: int = 1,
        overlap_merge: bool = False, merge_compression=None,
        merge_plan=None, merge_state: Optional[dict] = None,
        callback: Optional[Callable] = None,
        sample_seed: int = 0) -> FitResult:
    """Train any workload on the grid — THE entry point every layer
    above the algorithms (Trainer, configs, dry-run, benchmarks,
    examples) goes through.  Resolves the merge-plan spelling once
    (``None`` = exact default, a ``MergePlan``, or the string
    ``"auto"`` for the cost-model-driven self-tuning controller in
    ``repro.tuning``), applies the workload's ``merge_caps``
    (unsupported axes degrade with a ``MergeFallbackWarning``), and
    dispatches to the workload's ``run`` — the generic engine loop for
    gradient-style estimators, an algorithm-owned loop for the rest
    (dtree).

    Under ``jax.profiler`` a fit leaves host spans in the trace:
    ``pim.fit`` around this call, ``pim.prepare`` around the workload's
    ``prepare`` in :meth:`Workload.bind`, and from the scan engine
    (``PimGrid.fit``) ``pim.dispatch`` and ``pim.history`` around each
    chunk's runner call and its per-step unpacking, plus one
    ``pim.runner_build`` event per runner-cache miss
    (``PimGrid.make_runner``).  With the profiler off each costs under a
    microsecond of host time."""
    from repro.distributed import merge_plan as mp

    plan = mp.MergePlan.resolve(
        merge_plan, merge_every=merge_every, overlap_merge=overlap_merge,
        merge_compression=merge_compression)
    plan, batch_size = workload.merge_caps.constrain(
        workload.name, plan, batch_size)
    if getattr(X, "is_streaming_source", False):
        # out-of-core: X is a data.pipeline.StreamingDataset carrying
        # its own labels; the bound StreamProgram runs through the
        # identical engine loop (grid.fit dispatches the rotation)
        if y is not None:
            raise ValueError(
                "streaming fits carry labels inside the "
                "StreamingDataset — pass y=None")
        return workload.bind_stream(grid, X)._run(
            steps=steps, plan=plan, batch_size=batch_size, engine=engine,
            scan_chunk=scan_chunk, merge_state=merge_state,
            callback=callback, sample_seed=sample_seed)
    return workload.run(grid, X, y, steps=steps, plan=plan,
                        batch_size=batch_size, engine=engine,
                        scan_chunk=scan_chunk, merge_state=merge_state,
                        callback=callback, sample_seed=sample_seed)
