"""Roofline analysis from compiled (post-SPMD) HLO text.

``compiled.cost_analysis()`` counts a ``lax.scan`` body ONCE, so deep
scanned stacks are wildly undercounted.  This module parses the HLO
module instead:

  * computations are split into blocks; a call graph is built from
    ``calls=`` (fusions), ``body=``/``condition=`` (while loops) and
    ``branch_computations`` (conditionals);
  * while-loop trip counts are recovered from the largest integer
    constant in the loop's condition computation (scan lowering puts the
    trip count there);
  * ``dot`` FLOPs, per-op memory traffic and collective operand bytes
    are accumulated with the *product of enclosing trip counts*.

All numbers are per-device (post-partitioning shapes).  Terms:

  compute    = dot_flops / peaks.flops_bf16
  memory     = traffic_bytes / peaks.hbm_bw
  collective = Σ op_bytes * ring_factor(group) / peaks.ici_bw_per_link
               (DCN-aware: groups that span pods use
               peaks.dcn_bw_per_chip)

``peaks`` is a ``roofline.hw.ChipPeaks`` (default: the v5e target).
"""

from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from repro.roofline import hw

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
_OP_RE = re.compile(
    r"^\s*(%[\w\.\-]+|ROOT\s+%[\w\.\-]+)\s*=\s*"
    r"(\([^)]*\)|[a-z0-9]+\[[^\]]*\][^\s]*)\s+([\w\-]+)\((.*)$")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(.*\)\s*->.*\{")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _shape_dims(type_str: str) -> List[int]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return []
    return [int(d) for d in m.group(2).split(",") if d]


@dataclasses.dataclass
class Op:
    name: str
    type_str: str
    opcode: str
    rest: str           # operand list + attributes (raw tail of the line)

    def operands(self) -> List[str]:
        # names referenced before the closing paren of the op call
        depth, out, cur = 0, [], ""
        for ch in self.rest:
            if ch == "(":
                depth += 1
            elif ch == ")":
                if depth == 0:
                    out.append(cur)
                    break
                depth -= 1
            if depth == 0 and ch == ",":
                out.append(cur)
                cur = ""
            else:
                cur += ch
        names = []
        for tok in out:
            m = re.search(r"%[\w\.\-]+", tok)
            if m:
                names.append(m.group(0))
        return names

    def attr_comp(self, key: str) -> Optional[str]:
        m = re.search(key + r"=%?([\w\.\-]+)", self.rest)
        return m.group(1) if m else None

    def int_attr_list(self, key: str) -> List[int]:
        m = re.search(key + r"=\{([\d,\s]*)\}", self.rest)
        if not m:
            return []
        return [int(x) for x in m.group(1).split(",") if x.strip()]

    def replica_group_size(self) -> int:
        # replica_groups=[G,S]<=[...] -> group size S;
        # or explicit {{0,1},{2,3}} form
        m = re.search(r"replica_groups=\[([\d,]+)\]<=", self.rest)
        if m:
            dims = [int(x) for x in m.group(1).split(",")]
            return dims[-1] if dims else 1
        m = re.search(r"replica_groups=\{\{([^}]*)\}", self.rest)
        if m:
            return len([x for x in m.group(1).split(",") if x.strip()])
        return 1

    def replica_group_count(self) -> int:
        m = re.search(r"replica_groups=\[([\d,]+)\]<=", self.rest)
        if m:
            dims = [int(x) for x in m.group(1).split(",")]
            return dims[0] if len(dims) > 1 else 1
        return 1


@dataclasses.dataclass
class Computation:
    name: str
    ops: List[Op]
    symbols: Dict[str, str]      # op name -> type string


@dataclasses.dataclass
class CollectiveRec:
    opcode: str
    bytes: int
    group_size: int
    multiplier: float
    crosses_pod: bool


@dataclasses.dataclass
class ParsedHLO:
    computations: Dict[str, Computation]
    entry: str
    dot_flops: float = 0.0
    traffic_bytes: float = 0.0
    collectives: List[CollectiveRec] = dataclasses.field(
        default_factory=list)
    while_trips: Dict[str, int] = dataclasses.field(default_factory=dict)
    conv_flops: float = 0.0
    traffic_by_body: Dict[str, float] = dataclasses.field(
        default_factory=dict)          # computation -> bytes x trips
    dots_by_body: Dict[str, float] = dataclasses.field(
        default_factory=dict)

    def collective_bytes(self) -> float:
        return sum(c.bytes * c.multiplier for c in self.collectives)

    def summary(self) -> dict:
        per_op: Dict[str, float] = defaultdict(float)
        per_group: Dict[str, float] = defaultdict(float)
        for c in self.collectives:
            per_op[c.opcode] += c.bytes * c.multiplier
            per_group[f"{c.opcode}@g{c.group_size}"] += \
                c.bytes * c.multiplier
        return {
            "dot_flops": self.dot_flops,
            "conv_flops": self.conv_flops,
            "traffic_bytes": self.traffic_bytes,
            "collective_bytes": self.collective_bytes(),
            "collective_by_op": dict(per_op),
            "collective_by_group": dict(per_group),
            "while_trip_counts": self.while_trips,
        }


def parse_computations(text: str) -> Tuple[Dict[str, Computation], str]:
    comps: Dict[str, Computation] = {}
    entry = ""
    cur: Optional[Computation] = None
    for line in text.splitlines():
        stripped = line.rstrip()
        if cur is None:
            m = _COMP_RE.match(stripped)
            if m and stripped.endswith("{"):
                cur = Computation(m.group(1), [], {})
                if stripped.startswith("ENTRY"):
                    entry = m.group(1)
            continue
        if stripped.startswith("}"):
            comps[cur.name] = cur
            cur = None
            continue
        m = _OP_RE.match(stripped)
        if m:
            name = m.group(1).replace("ROOT", "").strip()
            op = Op(name, m.group(2), m.group(3), m.group(4))
            cur.ops.append(op)
            cur.symbols[name] = m.group(2)
    return comps, entry


def _trip_count(comps: Dict[str, Computation], cond_name: str) -> int:
    """Largest integer constant in the while condition (scan puts the trip
    count there; induction var starts at 0 so max() picks the bound)."""
    comp = comps.get(cond_name)
    if comp is None:
        return 1
    best = 1
    for op in comp.ops:
        if op.opcode == "constant":
            m = re.match(r"\s*(\d+)\s*\)?", op.rest)
            if m:
                best = max(best, int(m.group(1)))
    return best


def _dot_flops(comp: Computation, op: Op,
               comps: Dict[str, Computation]) -> float:
    out_elems = 1
    for d in _shape_dims(op.type_str):
        out_elems *= d
    # contraction size from lhs operand shape + contracting dims
    operands = op.operands()
    k = 1
    if operands:
        lhs_t = comp.symbols.get(operands[0])
        if lhs_t is None:
            for c in comps.values():
                if operands[0] in c.symbols:
                    lhs_t = c.symbols[operands[0]]
                    break
        cdims = op.int_attr_list("lhs_contracting_dims")
        if lhs_t is not None and cdims:
            dims = _shape_dims(lhs_t)
            for cd in cdims:
                if cd < len(dims):
                    k *= dims[cd]
    return 2.0 * out_elems * k


def _conv_flops(comp: Computation, op: Op,
                comps: Dict[str, Computation]) -> float:
    out_elems = 1
    for d in _shape_dims(op.type_str):
        out_elems *= d
    operands = op.operands()
    k = 1
    if len(operands) >= 2:
        rhs_t = comp.symbols.get(operands[1])
        if rhs_t:
            dims = _shape_dims(rhs_t)
            if dims:
                k = max(1, math.prod(dims) // max(1, dims[-1]))
    return 2.0 * out_elems * k


def analyze_hlo(text: str, pod_group_threshold: int = 2) -> ParsedHLO:
    """Walk the call graph from ENTRY accumulating trip-count-weighted
    dot FLOPs, per-op traffic and collective bytes.

    ``pod_group_threshold``: collectives whose replica group size equals
    the pod count (2) or whose groups span >256 device strides are
    attributed to the DCN hop.  With the (pod,data,model) mesh the pod
    axis is the slowest-varying, so a group that includes both pods has
    size divisible by 2 along that axis; we use the conservative rule
    group_size * group_count > 256 -> crosses pods when 512 devices.
    """
    comps, entry = parse_computations(text)
    parsed = ParsedHLO(comps, entry)
    n_devices_hint = 0
    m = re.search(r"<=\[(\d+)\]", text)
    if m:
        n_devices_hint = int(m.group(1))

    seen_stack: List[str] = []
    # ops that move no (or negligible) HBM bytes themselves; `copy` is
    # CPU copy-insertion at loop boundaries — TPU aliases loop carries
    # in place, so counting them would charge phantom traffic x trips
    _FREE = {"tuple", "get-tuple-element", "bitcast", "parameter",
             "constant", "reshape", "after-all", "iota", "while",
             "conditional", "call", "custom-call", "transpose",
             "copy", "copy-start", "copy-done"}

    def _traffic(comp: Computation, op: Op) -> float:
        oc = op.opcode
        if oc in _FREE:
            return 0.0
        if oc == "dynamic-slice":
            return 2.0 * _shape_bytes(op.type_str)      # read+write slice
        if oc == "dynamic-update-slice":
            ops_ = op.operands()
            upd = comp.symbols.get(ops_[1]) if len(ops_) > 1 else None
            return 2.0 * _shape_bytes(upd) if upd else \
                _shape_bytes(op.type_str)
        tb = float(_shape_bytes(op.type_str))            # output write
        for o in op.operands():
            t = comp.symbols.get(o)
            if t:
                tb += _shape_bytes(t)                    # operand reads
        return tb

    def visit(comp_name: str, mult: float, in_fusion: bool):
        comp = comps.get(comp_name)
        if comp is None or comp_name in seen_stack:
            return
        seen_stack.append(comp_name)
        for op in comp.ops:
            oc = op.opcode
            if oc == "dot":
                f = _dot_flops(comp, op, comps) * mult
                parsed.dot_flops += f
                parsed.dots_by_body[comp_name] = \
                    parsed.dots_by_body.get(comp_name, 0.0) + f
            elif oc == "convolution":
                parsed.conv_flops += _conv_flops(comp, op, comps) * mult
            elif oc == "while":
                cond = op.attr_comp("condition")
                body = op.attr_comp("body")
                trips = _trip_count(comps, cond) if cond else 1
                if body:
                    parsed.while_trips[body] = trips
                    visit(body, mult * trips, False)
            elif oc == "fusion":
                callee = op.attr_comp("calls")
                if callee:
                    # fused interiors are registers; count only the
                    # fusion's own operands/output (below), but still
                    # harvest dots from inside
                    visit(callee, mult, True)
            elif oc == "conditional":
                for cal in re.findall(r"%([\w\.\-]+)",
                                      op.rest.split("branch_computations")
                                      [-1])[:4]:
                    visit(cal, mult, in_fusion)
            base = oc.replace("-start", "")
            if base in _COLLECTIVES and not oc.endswith("-done"):
                b = _shape_bytes(op.type_str)
                gs = op.replica_group_size()
                gc = op.replica_group_count()
                crosses = (n_devices_hint >= 512 and gs >= 2 and
                           _group_spans_pods(op, n_devices_hint))
                parsed.collectives.append(
                    CollectiveRec(base, b, gs, mult, crosses))
            if not in_fusion:
                t = _traffic(comp, op) * mult
                parsed.traffic_bytes += t
                parsed.traffic_by_body[comp_name] = \
                    parsed.traffic_by_body.get(comp_name, 0.0) + t
        seen_stack.pop()

    if entry:
        visit(entry, 1.0, False)
    return parsed


def merge_overlap_report(text: str) -> dict:
    """Did the compiled module schedule the merge collectives so they can
    run behind local compute?  (The HLO-level acceptance check for
    ``PimGrid.fit(overlap_merge=True)`` — see ``launch.dryrun_pim``.)

    Looks inside every while body (the scanned rounds) at the *scheduled
    instruction order*, which is a valid topological order of the data
    dependencies:

    * on backends with async collectives (TPU/GPU), an
      ``all-reduce-start`` whose matching ``all-reduce-done`` has dot
      ops between them is literally overlapped — the dots execute while
      the reduction is in flight;
    * on sync-collective backends (XLA:CPU emits plain ``all-reduce``),
      a dot scheduled *after* an all-reduce in the same body proves the
      reduction does not depend on that dot — the structural
      independence the double-buffered pipeline creates, and exactly
      what a latency-hiding scheduler needs.  (A serial merge->update->
      compute chain can never schedule a dot after the all-reduce: every
      dot feeds the next round's reduction.)

    Dots nested in fusions count at the fusion's schedule position.
    """
    comps, entry = parse_computations(text)

    def has_dot(comp_name: str, seen=None) -> bool:
        seen = seen or set()
        if comp_name in seen:
            return False
        seen.add(comp_name)
        comp = comps.get(comp_name)
        if comp is None:
            return False
        for op in comp.ops:
            if op.opcode == "dot":
                return True
            if op.opcode == "fusion":
                callee = op.attr_comp("calls")
                if callee and has_dot(callee, seen):
                    return True
        return False

    bodies = []
    for comp in comps.values():
        for op in comp.ops:
            if op.opcode == "while":
                body = op.attr_comp("body")
                if body:
                    bodies.append(body)

    report = {"while_bodies": len(bodies), "async_pairs": 0,
              "async_pairs_straddling_dots": 0, "sync_all_reduces": 0,
              "dots_after_sync_all_reduce": 0, "overlapped": False}
    for body in bodies:
        comp = comps.get(body)
        if comp is None:
            continue
        events = []               # (pos, kind) kind: start/done/sync/dot
        for pos, op in enumerate(comp.ops):
            oc = op.opcode
            if oc == "all-reduce-start":
                events.append((pos, "start", op.name))
            elif oc == "all-reduce-done":
                events.append((pos, "done", op.operands()[:1]))
            elif oc == "all-reduce":
                events.append((pos, "sync", op.name))
            elif oc == "dot" or (oc == "fusion" and
                                 has_dot(op.attr_comp("calls") or "")):
                events.append((pos, "dot", op.name))
        starts = [e for e in events if e[1] == "start"]
        dones = [e for e in events if e[1] == "done"]
        syncs = [e for e in events if e[1] == "sync"]
        dots = [e[0] for e in events if e[1] == "dot"]
        report["async_pairs"] += len(starts)
        report["sync_all_reduces"] += len(syncs)
        for s in starts:
            # pair each start with the first later done
            later = [d for d in dones if d[0] > s[0]]
            if later and any(s[0] < p < later[0][0] for p in dots):
                report["async_pairs_straddling_dots"] += 1
        for s in syncs:
            report["dots_after_sync_all_reduce"] += sum(
                1 for p in dots if p > s[0])
    report["overlapped"] = bool(
        report["async_pairs_straddling_dots"]
        or report["dots_after_sync_all_reduce"])
    return report


def _group_spans_pods(op: Op, n_devices: int, pod_size: int = 256) -> bool:
    """A replica group crosses pods if it mixes device ids < pod_size and
    >= pod_size.  For iota-form groups [G,S]<=[..perm..] we approximate:
    groups of size > 1 whose stride pattern covers the full id space span
    pods when G*S == n_devices and S > n_devices // 2 ... conservative:
    treat groups with size >= n_devices (global collectives) or iota
    permutations listing the pod-major axis as spanning."""
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\]"
                  r"(?:T\(([\d,]+)\))?", op.rest)
    if not m:
        return False
    g, s = int(m.group(1)), int(m.group(2))
    if g * s < n_devices:
        return False
    if s > pod_size:
        return True
    dims = [int(x) for x in m.group(3).split(",")]
    perm = [int(x) for x in m.group(4).split(",")] if m.group(4) else \
        list(range(len(dims)))
    # the grouped ids are the trailing axes of the transposed iota; they
    # cross pods iff any of those axes has original-id stride >= pod_size
    # (with (pod,data,model) meshes, axis 0 is pod-major, stride 256)
    strides = {}
    acc = 1
    for ax in range(len(dims) - 1, -1, -1):
        strides[ax] = acc
        acc *= dims[ax]
    covered = 1
    for ax in reversed(perm):
        if covered >= s:
            break
        covered *= dims[ax]
        if strides[ax] >= pod_size and dims[ax] > 1:
            return True
    return False


def roofline_terms(parsed: ParsedHLO, cost: dict, *, n_chips: int,
                   per_device_program: bool = True,
                   peaks: hw.ChipPeaks = hw.TPU_V5E) -> dict:
    """Three-term roofline (seconds, per step) + bottleneck, priced at
    ``peaks`` (default: the v5e target)."""
    flops = parsed.dot_flops + parsed.conv_flops
    raw_flops = float(cost.get("flops", 0.0) or 0.0)
    raw_bytes = float(cost.get("bytes accessed", 0.0) or 0.0)

    compute_s = flops / peaks.flops_bf16
    memory_s = parsed.traffic_bytes / peaks.hbm_bw

    ici_s = 0.0
    dcn_s = 0.0
    for c in parsed.collectives:
        n = max(c.group_size, 1)
        if c.opcode == "all-reduce":
            factor = 2.0 * (n - 1) / n
        elif c.opcode in ("all-gather", "reduce-scatter", "all-to-all"):
            factor = (n - 1) / n
        else:  # collective-permute
            factor = 1.0
        t = c.bytes * c.multiplier * factor
        if c.crosses_pod:
            dcn_s += t / peaks.dcn_bw_per_chip
        else:
            ici_s += t / peaks.ici_bw_per_link
    collective_s = ici_s + dcn_s

    terms = {"compute_s": compute_s, "memory_s": memory_s,
             "collective_s": collective_s, "ici_s": ici_s, "dcn_s": dcn_s}
    bottleneck = max(("compute_s", "memory_s", "collective_s"),
                     key=lambda k: terms[k])
    step_s = max(compute_s, memory_s, collective_s)
    return {
        **{k: float(v) for k, v in terms.items()},
        "bottleneck": bottleneck,
        "step_time_bound_s": float(step_s),
        "hlo_flops_per_device": float(flops),
        "hlo_flops_global": float(flops * n_chips),
        "cost_analysis_flops_raw": raw_flops,
        "cost_analysis_bytes_raw": raw_bytes,
        "scan_undercount_factor": float(flops / raw_flops)
        if raw_flops else None,
    }


def predict_round(parsed: ParsedHLO, *, n_chips: int = 1,
                  cadence: int = 1, wire_bytes: float = 0.0,
                  overlap: bool = False, baseline_cadence: int = 1,
                  encode_bytes: float = 0.0,
                  wire_bw: float = None,
                  peaks: hw.ChipPeaks = hw.TPU_V5E) -> dict:
    """Per-round time prediction for a candidate merge plan — the
    consumable entry point the tuning layer builds its cost model on
    (``repro.tuning.CostModel``).

    ``parsed`` is the ``analyze_hlo`` of ONE lowered merge round at
    ``baseline_cadence`` (normally 1).  The prediction decomposes a
    candidate round into:

    * ``t_local_s`` — per-local-step compute/memory bound, read off the
      roofline terms of the lowered round and normalised by
      ``baseline_cadence``.  Kernel block shapes are already baked into
      the lowered HLO, so they enter the model through ``parsed``.
    * ``t_merge_s`` — the merge cost: the round's fast-hop collectives
      (``ici_s``) plus the slow "host hop" modelled analytically from
      the candidate's compressed ``wire_bytes`` over the DCN bandwidth
      (``max`` with the lowered ``dcn_s`` — the wire-bytes term models
      the same hop the HLO's cross-pod collectives implement, so the
      two are never double counted).  ``encode_bytes`` adds the
      encode/decode traffic a compressed wire costs (a few passes over
      the dense tree), so compression only wins when the wire saving
      beats its encode cost.  ``wire_bw`` overrides the slow hop's
      bandwidth (default ``peaks.dcn_bw_per_chip``): a single-chip grid
      has no inter-chip link at all — its "slow hop" is an in-memory
      reduction moving at ``peaks.hbm_bw`` — and pricing it at DCN speed
      would make compression look like a win on a hop that is pure
      compute (``repro.tuning.CostModel`` passes the right one).
    * a candidate round then costs ``cadence * t_local + t_merge``, or
      with ``overlap=True`` only the merge time that ``cadence`` local
      steps cannot hide.

    Returns a dict with those terms plus ``round_s`` and
    ``us_per_step`` (the ranking key).
    """
    terms = roofline_terms(parsed, {}, n_chips=n_chips, peaks=peaks)
    base = max(int(baseline_cadence), 1)
    t_local = max(terms["compute_s"], terms["memory_s"]) / base
    t_encode = float(encode_bytes) / peaks.hbm_bw
    bw = peaks.dcn_bw_per_chip if wire_bw is None else float(wire_bw)
    t_merge = terms["ici_s"] + t_encode + \
        max(terms["dcn_s"], float(wire_bytes) / bw)
    k = max(int(cadence), 1)
    exposed = max(0.0, t_merge - k * t_local) if overlap else t_merge
    round_s = k * t_local + exposed
    return {
        "cadence": k,
        "overlap": bool(overlap),
        "wire_bytes": float(wire_bytes),
        "t_local_s": float(t_local),
        "t_merge_s": float(t_merge),
        "exposed_merge_s": float(exposed),
        "round_s": float(round_s),
        "us_per_step": float(round_s / k * 1e6),
    }


# ---------------------------------------------------------------------------
# analytic model FLOPs (6·N·D convention) for the "useful compute" ratio
# ---------------------------------------------------------------------------

def model_flops(cfg, kind: str, batch: int, seq_len: int) -> dict:
    """MODEL_FLOPS = 6·N·T (train) / 2·N·T (prefill) / 2·N·B (decode),
    N = active non-embedding params (MoE: experts scaled by top_k/E),
    plus the causal-attention term.  Used for the
    MODEL_FLOPS / HLO_FLOPs usefulness ratio."""
    from repro.models import build as build_model  # local import (cycles)
    import jax as _jax

    model = build_model(cfg)
    p_shape = _jax.eval_shape(lambda: model.init(_jax.random.PRNGKey(0)))
    total = sum(int(p.size) for p in _jax.tree.leaves(p_shape))

    # subtract embedding table(s); count MoE experts at top_k/E utilization
    emb = 0
    moe_total = 0
    for path, leaf in _jax.tree_util.tree_leaves_with_path(p_shape):
        keys = [getattr(k, "key", "") for k in path]
        if keys and keys[-1] == "embed":
            emb += int(leaf.size)
        if "moe" in keys and keys[-1] in ("w_gate", "w_up", "w_down"):
            moe_total += int(leaf.size)
    n_active = total - emb - moe_total
    if cfg.moe is not None and moe_total:
        n_active += int(moe_total * cfg.moe.top_k / cfg.moe.n_experts)

    # attention context term (causal): fwd = 2·B·H·S²·Dh per attn layer
    pat = cfg.pattern
    n_attn = sum(1 for k in pat if k == "attn")
    n_local = sum(1 for k in pat if k == "local_attn")
    H, Dh = cfg.n_heads, cfg.hd
    W = cfg.window or seq_len

    if kind == "train":
        T = batch * seq_len
        param_f = 6.0 * n_active * T
        attn_f = 3.0 * (2.0 * batch * H * Dh *
                        (n_attn * seq_len ** 2 / 2
                         + n_local * seq_len * min(W, seq_len)))
        if cfg.encoder is not None:
            ec = cfg.encoder
            # encoder layers over n_ctx + cross attention S x n_ctx
            attn_f += 3.0 * 2.0 * batch * H * Dh * (
                ec.n_layers * ec.n_ctx ** 2
                + len(pat) * seq_len * ec.n_ctx)
    elif kind == "prefill":
        T = batch * seq_len
        param_f = 2.0 * n_active * T
        attn_f = 2.0 * batch * H * Dh * (
            n_attn * seq_len ** 2 / 2
            + n_local * seq_len * min(W, seq_len))
        if cfg.encoder is not None:
            ec = cfg.encoder
            attn_f += 2.0 * batch * H * Dh * (
                ec.n_layers * ec.n_ctx ** 2
                + len(pat) * seq_len * ec.n_ctx)
    else:  # decode: one token, context = seq_len
        T = batch
        param_f = 2.0 * n_active * T
        attn_f = 4.0 * batch * H * Dh * (
            n_attn * seq_len + n_local * min(W, seq_len))
        if cfg.encoder is not None:
            ec = cfg.encoder
            attn_f += 4.0 * batch * H * Dh * len(pat) * ec.n_ctx

    # SSD term (mamba2): intra-chunk ~ 2·B·S·Q·H·(N+2P) per layer, fwd
    ssd_f = 0.0
    if cfg.ssm is not None:
        sc = cfg.ssm
        d_in = sc.expand * cfg.d_model
        Hs = d_in // sc.head_dim
        n_ssd = sum(1 for k in pat if k == "mamba2")
        if kind == "decode":
            ssd_f = 2.0 * batch * n_ssd * Hs * sc.head_dim * sc.d_state * 2
        else:
            Q = sc.chunk
            per_tok = 2.0 * Q * Hs * (sc.d_state + 2 * sc.head_dim)
            mult = 3.0 if kind == "train" else 1.0
            ssd_f = mult * batch * seq_len * per_tok * n_ssd

    total_f = param_f + attn_f + ssd_f
    return {"model_flops": float(total_f),
            "param_flops": float(param_f),
            "attn_flops": float(attn_f),
            "ssd_flops": float(ssd_f),
            "n_active_params": int(n_active),
            "n_total_params": int(total)}
