"""Dry-run of the PAPER'S OWN workloads at pod scale: a Workload plugin
(logistic regression by default, ``--workload svm`` / ``multinomial``
for the PIM-Opt companions) on a PimGrid of 4,096 virtual DPUs spread
over the production mesh (the paper's 2,524-DPU system, scaled up),
with the int8 resident dataset (I1), LUT activations (I2) and
hierarchical ICI-then-DCN merge (I5).

  PYTHONPATH=src python -m repro.launch.dryrun_pim [--multi-pod]
      [--workload {logreg,svm,multinomial}] [--batch-size B]
      [--merge-every K] [--chunk L] [--rows N]
      [--overlap-merge] [--compress-bits B]

Aligned with the scan step engine (PR 1/2): what lowers here is the
grid's own cached chunk runner — ``PimGrid.make_runner`` scanning
``--chunk`` merge rounds at cadence ``--merge-every`` — with the inner
loop routed through ``kernels.dispatch`` exactly like the mlalgos.
The step functions come from the Workload protocol
(``workload.spec_fns``: the same ``local_step``/``update`` the training
entry points run, assembled over spec-level constants so no dataset is
materialized), so a new estimator plugin is pod-lowerable with zero
dry-run changes.  The collective schedule in the compiled HLO *is* the
paper's host-merge (all-reduce@data groups then all-reduce@pod groups),
and at cadence k it appears once per k local steps instead of every
step.  ``--batch-size B`` wraps the fns in the on-device minibatch
sampler (``core.minibatch``) — the lowered scan then carries the
sampler's step counter and gathers B resident rows per vDPU per step.

``--overlap-merge`` lowers the double-buffered pipeline instead and
then *verifies the overlap in the compiled HLO*
(``roofline.analysis.merge_overlap_report``): on async-collective
backends the ``all-reduce-start``/``all-reduce-done`` pairs must
straddle local-compute dots; on sync backends (XLA:CPU emits plain
``all-reduce``) dots scheduled after the merge all-reduce prove the
reduction is independent of this round's compute — the structural
precondition the latency-hiding scheduler needs.  The run fails if the
pipeline did not decouple the merge from the dots.  ``--compress-bits``
adds the int8/int16 error-feedback wire on the slow hop.

``--merge-plan {avg,slowmo,nesterov,topk}`` lowers the composed
``distributed.merge_plan`` runner instead: ``slowmo``/``nesterov`` add
the outer-momentum buffer to the scan carry, ``topk`` puts the top-k
error-feedback sparsifier on the slow hop.  All compose with
``--overlap-merge`` (the HLO overlap report applies unchanged) and
``--merge-every``.  ``adaptive`` is deliberately not lowered here: the
controller is host-side and reuses the per-cadence runners this dry-run
already lowers.  ``--merge-plan auto`` runs the self-tuning layer's
*cost-model pass* instead (``repro.tuning.CostModel`` on the lowered
HLO of one merge round): the output JSON gains an ``auto_plan`` section
with the chosen ``(cadence, wire format)``, per-format wire bytes, and
the full ranked cost table, and the lowered artifact is the prior-best
state-wire pipeline runner — the same one ``fit(merge_plan="auto")``
dispatches on its first exploitation round.  Any
``MergeFallbackWarning`` raised while building is surfaced in the
output JSON (``merge_fallback_warnings``).
"""

import argparse
import dataclasses
import json
import os

import jax
import jax.numpy as jnp

from repro.core.pim import PimGrid
from repro.core import minibatch as mb
from repro.configs.pim_ml import CONFIG
from repro.launch.mesh import make_production_mesh
from repro.roofline import analysis as ra

# the pod-lowerable gradient workloads: int8 resident dataset + LUT
# activations, exactly what the training entry points run.  The
# name -> estimator mapping is the config's (PimMLConfig.workload_spec)
# so hyperparameters live in one place.
WORKLOAD_NAMES = ("logreg", "svm", "multinomial")


def _workload(name: str):
    return dataclasses.replace(CONFIG, workload=name).workload_spec(
        precision="int8")


def build(multi_pod: bool, n_vdpus: int = 4096, rows: int = 1 << 24,
          features: int = 64, merge_every: int = 1, chunk: int = 8,
          overlap: bool = False, compress_bits: int = 0,
          plan_name: str = "avg", workload: str = "logreg",
          batch_size: int = 0):
    mesh = make_production_mesh(multi_pod=multi_pod)
    data_axes = ("pod", "data") if multi_pod else ("data",)
    grid = PimGrid(n_vdpus=n_vdpus, mesh=mesh, data_axes=data_axes)
    per = rows // n_vdpus

    wl = _workload(workload)
    local_fn, update_fn, state0, consts = wl.spec_fns(features=features,
                                                      rows=rows)
    if batch_size:
        local_fn, update_fn, state0, _ = mb.minibatch_fns(
            local_fn, update_fn, state0, rows_per_vdpu=per,
            batch_size=batch_size)

    y_dtype = jnp.int32 if workload == "multinomial" else jnp.float32
    data_spec = {
        "X": jax.ShapeDtypeStruct((n_vdpus, per, features), jnp.int8,
                                  sharding=grid.data_sharding()),
        "y0": jax.ShapeDtypeStruct((n_vdpus, per), y_dtype,
                                   sharding=grid.data_sharding()),
        "w": jax.ShapeDtypeStruct((n_vdpus, per), jnp.float32,
                                  sharding=grid.data_sharding()),
    }
    w_spec, consts_spec = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(tuple(x.shape), x.dtype,
                                       sharding=grid.replicated_sharding()),
        (state0, consts))

    from repro.distributed import merge_plan as mp
    from repro.distributed.compression import CompressionConfig

    compression = None
    if compress_bits:
        compression = CompressionConfig(bits=compress_bits)
    outer = mp.AverageCommit()
    extra = {}
    force_pipeline = False
    if plan_name == "slowmo":
        outer = mp.SlowMo(beta=0.5)
    elif plan_name == "nesterov":
        outer = mp.Nesterov(beta=0.5)
    elif plan_name == "topk":
        compression = CompressionConfig(
            bits=compress_bits or None, top_k_frac=0.125)
    elif plan_name == "auto":
        # the self-tuning layer's cost-model pass over the candidate
        # grid: rank (cadence, wire-format) tuples from the lowered
        # HLO of one merge round, emit the table, then lower the
        # prior-best runner — the same artifact the controller's first
        # exploitation round dispatches
        from repro import tuning

        preset = tuning.AutoTune()
        model = tuning.CostModel.for_fit(grid,
                                         mp.bind_consts(local_fn, consts),
                                         mp.bind_consts(update_fn, consts),
                                         w_spec, data_spec)
        # the candidate grid is (wire format x overlap); the table
        # enumerates the unique wires against both overlap settings
        choices = tuning.candidate_choices(preset, compression)
        wires, seen = [], set()
        for c in choices:
            wt = tuning.compression_tag(c.compression)
            if wt not in seen:
                seen.add(wt)
                wires.append(c.compression)
        cadences = tuning.cadence_ladder(max(merge_every, 1),
                                         preset.k_max, preset.growth)
        table = model.table(
            cadences=cadences, compressions=wires,
            overlaps=tuple(sorted({c.overlap for c in choices})))
        best = table[0]
        extra["auto_plan"] = {
            "chosen": {"cadence": int(best["cadence"]),
                       "compression": best["compression"],
                       "overlap": bool(best["overlap"])},
            "wire_bytes_by_format": {
                tuning.compression_tag(w): int(model.wire_bytes(w))
                for w in wires},
            "cost_table": table,
        }
        merge_every = int(best["cadence"])
        compression = {tuning.compression_tag(w): w
                       for w in wires}[best["compression"]]
        overlap = bool(best["overlap"])
        force_pipeline = True      # auto fits run the state-wire
        # pipeline runner whatever the chosen wire format
    elif plan_name != "avg":
        raise SystemExit(
            f"--merge-plan {plan_name!r} is not lowerable here (the "
            f"adaptive controller is host-side; see module docstring)")
    plan = mp.MergePlan(cadence=merge_every, overlap=overlap,
                        compression=compression, outer=outer)

    if batch_size and not plan.outer.plain_commit:
        raise SystemExit(
            "--batch-size cannot compose with a stateful outer "
            "optimizer (the sampler's step counter would be folded "
            "into its momentum — see core.mlalgos.api)")

    if plan.is_exact_default and not force_pipeline:
        # the scan engine's own cached chunk runner — the artifact the
        # fit hot path dispatches, scanning `chunk` merge rounds
        runner = grid.make_runner(local_fn, update_fn,
                                  merge_every=merge_every)
        lowered = runner.lower(w_spec, data_spec, consts_spec,
                               length=chunk)
        return lowered, lowered.compile(), mesh, extra

    # plan modes: lower the composed runner on its own carry layout —
    # (state[, pending], ef, mom); see distributed.merge_plan.run_fit
    from jax.sharding import NamedSharding, PartitionSpec as P
    state_wire = merge_every > 1 or force_pipeline
    rs = mp.pipeline_runners(grid, local_fn, update_fn,
                             merge_every=merge_every, overlap=overlap,
                             compression=compression,
                             state_wire=state_wire, outer=outer)
    runner = rs["runner"]
    wire = mp.wire_spec(grid, local_fn, update_fn, w_spec, data_spec,
                        merge_every=merge_every, consts=consts_spec)
    lanes_sharding = grid.data_sharding()
    pending_spec = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n_vdpus,) + tuple(s.shape),
                                       s.dtype, sharding=lanes_sharding),
        wire)
    if state_wire:
        # delayed-delta pending: (per-lane phase-end states, start anchor)
        pending_spec = (pending_spec, w_spec)
    ef_spec = None
    if compression is not None:
        hop_sharding = NamedSharding(mesh, P(data_axes[0]))
        ef_spec = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                (mp.hop_size(grid),) + tuple(s.shape), s.dtype,
                sharding=hop_sharding),
            wire)
    mom_spec = ()
    if not outer.plain_commit:
        mom_spec = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                tuple(s.shape), s.dtype,
                sharding=grid.replicated_sharding()),
            jax.eval_shape(outer.init, w_spec))
    if overlap:
        carry = (w_spec, pending_spec, ef_spec, mom_spec)
    else:
        carry = (w_spec, ef_spec, mom_spec)
    lowered = runner.lower(carry, data_spec, consts_spec, length=chunk)
    return lowered, lowered.compile(), mesh, extra


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--rows", type=int, default=1 << 24)
    ap.add_argument("--workload", default=CONFIG.workload,
                    choices=WORKLOAD_NAMES,
                    help="which Workload plugin to lower (all int8 "
                         "resident + LUT activations; default from "
                         "configs.pim_ml)")
    ap.add_argument("--batch-size", type=int, default=CONFIG.batch_size,
                    help="on-device minibatch sampling: resident rows "
                         "per vDPU per local step (0 = full batch; "
                         "default from configs.pim_ml)")
    ap.add_argument("--merge-every", type=int, default=1,
                    help="vDPU-local steps per hierarchical merge")
    ap.add_argument("--chunk", type=int, default=8,
                    help="merge rounds per scanned host dispatch")
    ap.add_argument("--overlap-merge", action="store_true",
                    help="lower the double-buffered merge pipeline and "
                         "verify the collective/dot schedule overlaps")
    ap.add_argument("--compress-bits", type=int, default=0,
                    help="error-feedback fixed-point width on the slow "
                         "hop (0 = exact merges)")
    ap.add_argument("--merge-plan", default="avg",
                    choices=("avg", "slowmo", "nesterov", "topk",
                             "auto"),
                    help="composed merge plan to lower: slowmo/nesterov "
                         "add the outer-momentum carry leaf, topk the "
                         "top-k EF sparsifier on the slow hop; auto "
                         "runs the repro.tuning cost model over the "
                         "candidate grid, emits the ranked cost table "
                         "+ chosen plan, and lowers the prior-best "
                         "runner")
    args = ap.parse_args()

    import warnings as _warnings
    from repro.distributed.merge_plan import MergeFallbackWarning
    with _warnings.catch_warnings(record=True) as caught:
        _warnings.simplefilter("always", MergeFallbackWarning)
        lowered, compiled, mesh, extra = build(
            args.multi_pod, rows=args.rows,
            merge_every=args.merge_every, chunk=args.chunk,
            overlap=args.overlap_merge,
            compress_bits=args.compress_bits,
            plan_name=args.merge_plan, workload=args.workload,
            batch_size=args.batch_size)
    fallback_warnings = [str(w.message) for w in caught
                         if issubclass(w.category, MergeFallbackWarning)]
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, (list, tuple)):      # one entry per program in
        cost = cost[0] if cost else {}       # newer jax versions
    hlo_text = compiled.as_text()
    parsed = ra.analyze_hlo(hlo_text)
    n_chips = 512 if args.multi_pod else 256
    terms = ra.roofline_terms(parsed, cost, n_chips=n_chips)
    tag = "pod2x16x16" if args.multi_pod else "pod16x16"
    arch = f"pim-ml({args.workload},int8+lut,scan-engine"
    if args.batch_size:
        arch += f",b{args.batch_size}"
    if args.overlap_merge:
        arch += ",overlap"
    if args.compress_bits:
        arch += f",efq{args.compress_bits}"
    if args.merge_plan != "avg":
        arch += f",{args.merge_plan}"
    arch += ")"
    out = {
        "arch": arch, "mesh": tag,
        "rows": args.rows, "n_vdpus": 4096,
        "workload": args.workload, "batch_size": args.batch_size,
        "merge_every": args.merge_every, "scan_chunk": args.chunk,
        "overlap_merge": args.overlap_merge,
        "compress_bits": args.compress_bits,
        "merge_plan": args.merge_plan,
        "merge_fallback_warnings": fallback_warnings,
        "memory_gb_per_device": round(
            (mem.argument_size_in_bytes + mem.temp_size_in_bytes)
            / 2 ** 30, 3),
        "roofline": terms,
        "collectives": parsed.summary()["collective_by_group"],
    }
    out.update(extra)              # auto: chosen plan + ranked cost table
    if args.overlap_merge:
        report = ra.merge_overlap_report(hlo_text)
        out["merge_overlap"] = report
        if not report["overlapped"]:
            # a hard failure, not an assert: this gate must hold under
            # `python -O` too
            raise SystemExit(
                "overlap_merge lowered a schedule where every dot "
                "precedes the merge all-reduce — pipeline not "
                f"decoupled: {report}")
        print("merge overlap verified:", json.dumps(report))
    path = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                        "experiments", "dryrun", f"pim-ml_{tag}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    # must be set before the first jax backend init; override to
    # smoke-test the lowering on fewer fake devices (the default meshes
    # need 256/512)
    os.environ.setdefault(
        "XLA_FLAGS",
        "--xla_force_host_platform_device_count="
        + os.environ.get("REPRO_DRYRUN_DEVICES", "512"))
    main()
