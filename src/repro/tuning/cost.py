"""The roofline-backed cost model behind ``merge_plan="auto"``.

``CostModel`` turns the ~500-line HLO analysis in ``roofline/analysis``
into something the plan controller can actually consume: given the
lowered HLO of ONE merge round of the already-compiled program, it
predicts per-round time and wire bytes for any candidate ``(cadence,
compression, overlap)`` tuple.  Kernel block shapes need no explicit
axis here — they are baked into the lowered round the model reads, so
re-tuning blocks (``tuning.autotune``) refreshes the prior the next
time the model is built.

The prediction deliberately has the same shape as the scaling study's
fitted speedup model (``benchmarks/bench_scaling.py``):

    us_per_step(k, cfg) = t_local + t_merge(cfg) / k

so measured round times refine exactly the two coefficients the prior
guesses — the controller never has to reconcile two different models.

The model is built once per ``(grid, fns, kernels-flag)`` and cached on
the grid's compile cache: lowering is a trace (no compilation), and the
cadence-1 state-wire round it lowers is the same runner the controller's
first round uses, so the work is shared, not extra.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Sequence

import jax

from repro.distributed import compression as comp
from repro.distributed.compression import CompressionConfig
from repro.roofline import analysis as ra
from repro.roofline import hw
from repro.tuning.measurement import Measurement


def compression_tag(cfg: Optional[CompressionConfig]) -> str:
    """Compact JSON-friendly label for a wire format: ``"exact"``,
    ``"int8"``, ``"top0.125@int8"``, ``"top0.25@raw"``."""
    if cfg is None:
        return "exact"
    bits = "raw" if cfg.bits is None else f"int{cfg.bits}"
    if cfg.top_k_frac is not None:
        return f"top{cfg.top_k_frac:g}@{bits}"
    return bits


def _dense_float_bytes(wire: Any) -> int:
    """Dense float bytes of the wire tree — the traffic one
    encode/decode pass over it costs."""
    total = 0
    for leaf in jax.tree.leaves(wire):
        size = 1
        for d in leaf.shape:
            size *= int(d)
        total += size * leaf.dtype.itemsize
    return total


@dataclasses.dataclass
class CostModel:
    """Per-round time + wire-byte predictions from one lowered round.

    ``parsed`` is ``analyze_hlo`` of a cadence-1 state-wire round;
    ``wire`` is the state-shaped ShapeDtypeStruct tree that crosses the
    slow hop at cadence > 1 (the tree every controller round ships).
    """

    parsed: ra.ParsedHLO
    wire: Any
    n_chips: int = 1
    baseline_cadence: int = 1
    peaks: hw.ChipPeaks = hw.TPU_V5E

    # encode/decode passes a compressed wire costs over the dense tree
    # (quantize + dequantize + error-feedback update)
    ENCODE_PASSES = 3

    @classmethod
    def for_fit(cls, grid, local_fn, update_fn, state, data
                ) -> "CostModel":
        """Build (or fetch from the grid compile cache) the model for
        one fit's functions.  ``state``/``data`` may be concrete arrays
        or ShapeDtypeStructs — lowering only traces."""
        from repro.distributed import merge_plan as mp
        from repro.kernels.dispatch import kernels_enabled

        key = ("tuning_cost_model", mp.fn_signature(local_fn),
               mp.fn_signature(update_fn), kernels_enabled())
        hit = mp.cache_get(grid, key)
        if hit is not None:
            return hit
        rs = mp.pipeline_runners(
            grid, local_fn, update_fn, merge_every=1, overlap=False,
            compression=None, state_wire=True, outer=mp.AverageCommit())
        lowered = rs["round"].lower((state, None, ()), data)
        parsed = ra.analyze_hlo(lowered.as_text())
        wire = mp.wire_spec(grid, local_fn, update_fn, state, data,
                            merge_every=2)
        n_chips = 1 if grid.mesh is None else grid.mesh.size
        device = (jax.devices()[0] if grid.mesh is None
                  else grid.mesh.devices.flat[0])
        model = cls(parsed=parsed, wire=wire, n_chips=int(n_chips),
                    peaks=hw.peaks_for(device))
        mp.cache_put(grid, key, model, local_fn, update_fn)
        return model

    def wire_bytes(self, compression: Optional[CompressionConfig]) -> int:
        return comp.wire_bytes(self.wire, compression)

    def predict(self, *, cadence: int = 1,
                compression: Optional[CompressionConfig] = None,
                overlap: bool = False) -> dict:
        """Predicted cost row for one candidate tuple.

        On a single-chip grid (``n_chips == 1`` — the emulated vmap
        grid) the slow hop is an in-memory reduction, so its wire
        moves at HBM bandwidth: compression can then never win on
        modeled time (one dense pass always beats ENCODE_PASSES of
        them plus the compressed wire), which matches what measuring
        the emulation shows.  Across a real mesh the wire is priced at
        the DCN link, where sending fewer bytes is a real saving."""
        encode = 0 if compression is None \
            else self.ENCODE_PASSES * _dense_float_bytes(self.wire)
        # overlap can only hide merge time behind compute when there is
        # a second execution stream to hide it in: on a single-chip
        # (emulated) grid the "wire" is an in-memory reduction on the
        # same device, so overlap=True buys nothing and the prior must
        # say so — only a measurement may promote it (the controller's
        # probe round), never the model
        hides = overlap and self.n_chips > 1
        row = ra.predict_round(
            self.parsed, n_chips=self.n_chips, cadence=cadence,
            wire_bytes=self.wire_bytes(compression), overlap=hides,
            baseline_cadence=self.baseline_cadence,
            encode_bytes=encode,
            wire_bw=self.peaks.hbm_bw if self.n_chips == 1 else None,
            peaks=self.peaks)
        row["compression"] = compression_tag(compression)
        row["overlap"] = bool(overlap)
        return row

    def prediction(self, *, cadence: int = 1,
                   compression: Optional[CompressionConfig] = None,
                   overlap: bool = False) -> Measurement:
        """The same prediction as :meth:`predict`, spoken as the shared
        ``Measurement`` record (``source="prior"``)."""
        row = self.predict(cadence=cadence, compression=compression,
                           overlap=overlap)
        return Measurement(
            key=("plan", int(cadence), compression_tag(compression),
                 bool(overlap)),
            seconds=row["round_s"], steps=int(cadence), source="prior")

    def table(self, *, cadences: Sequence[int],
              compressions: Sequence[Optional[CompressionConfig]],
              overlaps: Sequence[bool] = (False,)) -> List[dict]:
        """Cost rows for a candidate grid, best (lowest predicted
        us_per_step) first — the table ``dryrun_pim --merge-plan auto``
        emits and ``merge_state["tuning_trace"]`` records."""
        rows = [self.predict(cadence=k, compression=c, overlap=o)
                for k in cadences for c in compressions for o in overlaps]
        rows.sort(key=lambda r: r["us_per_step"])
        return rows
