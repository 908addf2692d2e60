"""Static HLO profiler: trip-count-aware flops / traffic / collective stats.

XLA's `compiled.cost_analysis()` counts every while-loop body ONCE — a
layer-scanned transformer therefore under-reports flops by ~num_layers x
(verified against a known matmul scan in this environment).  This module
re-derives costs from `compiled.as_text()`:

  1. parse every computation and its ops (with a per-computation symbol
     table of result shapes),
  2. build the call graph (fusion `calls=`, `to_apply=`, while `body=` /
     `condition=`) and propagate a *multiplicity* from ENTRY, multiplying
     by the while trip count (extracted from the loop-condition's compare
     constant),
  3. accumulate, weighted by multiplicity:
       - dot flops          2 * numel(result) * prod(contracting dims)
       - dot traffic bytes  operands + result (an upper bound on HBM
         traffic that ignores fusion reuse; elementwise ops excluded)
       - collective link bytes (same algorithm factors as hlostats)

All numbers are PER-DEVICE (the compiled module is the SPMD partition).
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*->.*\{\s*$")
_OP_LINE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_SHAPE = re.compile(r"^(\(?)((?:\w+\[[\d,]*\](?:\{[^}]*\})?(?:,\s*)?)+)\)?")
_ONE_SHAPE = re.compile(r"(\w+)\[([\d,]*)\]")
_OPNAME = re.compile(r"^\s*([\w\-]+)\(")
_CALLS = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_WHILE = re.compile(r"condition=%?([\w.\-]+),\s*body=%?([\w.\-]+)")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
# operand list of a dot/convolution: `dot(f32[8,64]{1,0} %a, f32[64,64]{1,0} %b)`
# (current printers include the operand shape inline) or `dot(%a, %b)`
# (older printers — resolve through the computation symbol table).
_OP_PARENS = re.compile(r"^\s*(?:dot|convolution)\((.*?)\)")
_OPERAND_ENTRY = re.compile(
    r"((?:\w+\[[\d,]*\](?:\{[^}]*\})?)\s+)?%([\w.\-]+)")
# kernel dim labels of a convolution: dim_labels=b01f_01io->b01f
_DIM_LABELS = re.compile(r"dim_labels=[\w?]+_([\w?]+)->")
# XLA records the resolved trip count on the while op itself:
#   backend_config={"known_trip_count":{"n":"7"},...}
_KNOWN_TRIPS = re.compile(r"known_trip_count[^0-9}]*\"n\"\s*:\s*\"(\d+)\"")
_COMPARE_CONST = re.compile(r"constant\((\d+)\)")
_GROUPS_EXPLICIT = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")

_COLL_KINDS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")


def _shape_info(text: str) -> Tuple[int, int]:
    """(numel, bytes) summed over tuple elements of a shape string."""
    numel = total = 0
    for m in _ONE_SHAPE.finditer(text):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        numel += n
        total += n * _DTYPE_BYTES[dt]
    return numel, total


@dataclasses.dataclass
class Op:
    name: str
    kind: str
    shape_str: str
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    ops: List[Op]
    shapes: Dict[str, str]          # %name -> shape string


_KIND_RE = re.compile(r"(?:^|\s)([\w\-]+)\(")


def parse_computations(hlo: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        if cur is None:
            m = _COMP_HDR.match(line.strip())
            if m and line.rstrip().endswith("{"):
                cur = Computation(m.group(1), [], {})
            continue
        if line.strip() == "}":
            comps[cur.name] = cur
            cur = None
            continue
        m = _OP_LINE.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        km = _KIND_RE.search(rest)
        if km is None:
            continue
        kind = km.group(1)
        shape_str = rest[:km.start()].strip()
        # op body from the kind keyword onward (operands, attributes)
        cur.shapes[name] = shape_str
        cur.ops.append(Op(name, kind, shape_str, rest[km.start():]))
    return comps


def _trip_count(cond: Computation) -> int:
    """Trip count from the loop condition's compare constant (best effort)."""
    best = 1
    for op in cond.ops:
        if op.kind == "compare" or "compare(" in op.line:
            c = _COMPARE_CONST.search(op.line)
            if c:
                best = max(best, int(c.group(1)))
    if best == 1:  # constant may be defined on its own line
        consts = [int(c) for op in cond.ops
                  for c in _COMPARE_CONST.findall(op.line)]
        if consts:
            best = max(consts)
    return max(best, 1)


def _while_trips(op: Op, comps: Dict[str, Computation], cond_name: str) -> int:
    """Trip count of one while op: XLA's known_trip_count backend_config
    when present, else the loop-condition's compare constant."""
    tm = _KNOWN_TRIPS.search(op.line)
    if tm:
        return int(tm.group(1))
    if cond_name in comps:
        return _trip_count(comps[cond_name])
    return 1


def _multiplicities(comps: Dict[str, Computation], entry: str) -> Dict[str, float]:
    mult: Dict[str, float] = defaultdict(float)
    mult[entry] = 1.0
    order = [entry]
    seen = {entry}
    # propagate breadth-first; HLO call graphs are acyclic
    i = 0
    while i < len(order):
        cname = order[i]
        i += 1
        comp = comps.get(cname)
        if comp is None:
            continue
        m = mult[cname]
        for op in comp.ops:
            wm = _WHILE.search(op.line) if op.kind == "while" else None
            if wm:
                cond_name, body_name = wm.group(1), wm.group(2)
                trips = _while_trips(op, comps, cond_name)
                for callee, f in ((body_name, trips), (cond_name, trips + 1)):
                    mult[callee] += m * f
                    if callee not in seen:
                        seen.add(callee)
                        order.append(callee)
                continue
            cm = _CALLS.search(op.line)
            if cm:
                callee = cm.group(1)
                mult[callee] += m
                if callee not in seen:
                    seen.add(callee)
                    order.append(callee)
    return mult


def _operand_shapes(comp: Computation, op: Op) -> List[str]:
    """Shape strings of a dot/convolution's operands.  Prefers the shapes the
    printer writes inline (`dot(f32[8,64]{1,0} %a, ...)`); falls back to the
    computation symbol table for bare `%name` operands."""
    m = _OP_PARENS.match(op.line)
    if not m:
        return []
    out = []
    for em in _OPERAND_ENTRY.finditer(m.group(1)):
        shape = em.group(1) or comp.shapes.get(em.group(2), "")
        out.append(shape.strip())
    return out


def _conv_contract(op: Op, shapes: List[str]) -> int:
    """Per-output-element MACs of a convolution: kernel spatial numel times
    input features == rhs numel / output features (via dim_labels)."""
    if len(shapes) < 2:
        return 0
    dm = _DIM_LABELS.search(op.line)
    rhs = _ONE_SHAPE.search(shapes[1])
    if not (dm and rhs):
        return 0
    kdims = [int(d) for d in rhs.group(2).split(",") if d]
    labels = dm.group(1)
    if "o" not in labels or len(labels) != len(kdims):
        return 0
    contract = 1
    for i, lbl in enumerate(labels):
        if lbl != "o":
            contract *= kdims[i]
    return contract


def _dot_flops(comp: Computation, op: Op) -> Tuple[float, float]:
    """(flops, traffic_bytes) for a dot/convolution op.

    Raises ValueError when the op line cannot be parsed — a silent
    contract=1 / operand_bytes=0 fallback under-counts flops by ~1000x and
    poisons every downstream roofline figure (it happened)."""
    out_numel, out_bytes = _shape_info(op.shape_str)
    shapes = _operand_shapes(comp, op)
    operand_bytes = sum(_shape_info(s)[1] for s in shapes)
    contract = 0
    if op.kind == "convolution":
        contract = _conv_contract(op, shapes)
    else:
        cm = _CONTRACT.search(op.line)
        if cm and shapes:
            lhs_dims = _ONE_SHAPE.search(shapes[0])
            if lhs_dims:
                contract = 1
                dim_list = [int(d) for d in lhs_dims.group(2).split(",") if d]
                for ds in cm.group(1).split(","):
                    if not ds:
                        continue
                    idx = int(ds)
                    if idx >= len(dim_list):
                        contract = 0
                        break
                    contract *= dim_list[idx]
    if contract <= 0 or operand_bytes <= 0:
        raise ValueError(
            f"hloprof could not parse {op.kind} operands/contracting dims "
            f"(contract={contract}, operand_bytes={operand_bytes}): {op.line[:200]}")
    return 2.0 * out_numel * contract, float(out_bytes + operand_bytes)


def _coll_factor(kind: str, n: int) -> float:
    if n <= 1:
        return 0.0
    if kind.startswith("all-gather"):
        return (n - 1) / n
    if kind.startswith("all-reduce"):
        return 2 * (n - 1) / n
    if kind == "reduce-scatter":
        return float(n - 1)
    if kind == "all-to-all":
        return (n - 1) / n
    return 1.0


_CALL_PARENS = re.compile(r"^\s*[\w\-]+\((.*?)\)")


def cpu_upcast_bytes(hlo: str) -> int:
    """Bytes of materialized bf16->f32 upcast buffers.  The CPU host backend
    has no native bf16 matmul and materializes f32 copies of bf16 weights —
    as `parallel_convert*` call wrappers or kLoop `wrapped_convert` fusions.
    Printers differ on whether a call's parentheses carry operand types, so
    an untyped operand is typed by its defining op.  TPU executes bf16 dots
    natively, so these buffers would not exist on the target; subtract from
    peak memory for the TPU-projected figure.

    Detection: in every *sequential* computation (entry / while bodies —
    i.e. not itself the target of a `calls=`/`to_apply=` edge, whose ops are
    counted at their call site instead), a materialized upcast is an op
    whose f32 result has the same numel as a bf16 operand and is either a
    plain `convert` or a call/fusion into a convert wrapper (op or callee
    name contains "convert" — the CPU backend's parallel_convert / kLoop
    convert idiom).  The name filter keeps e.g. a softmax fusion that
    happens to widen bf16 activations (present on TPU too) out of the
    count; a plain logits upcast still counts, so treat the figure as a
    best-effort projection, not an exact TPU peak.  Each buffer is counted
    once (buffers are reused across loop trips)."""
    return _upcast_bytes_from_comps(parse_computations(hlo))


def _upcast_bytes_from_comps(comps: Dict[str, Computation]) -> int:
    called = set()
    for comp in comps.values():
        for op in comp.ops:
            m = _CALLS.search(op.line)
            if m:
                called.add(m.group(1))

    def _numel(m: "re.Match") -> int:
        return _shape_info(m.group(0))[0]

    total = 0
    for cname, comp in comps.items():
        if cname in called:
            continue
        for op in comp.ops:
            if op.kind not in ("convert", "call", "fusion"):
                continue
            if op.kind != "convert":
                cm = _CALLS.search(op.line)
                callee = cm.group(1) if cm else ""
                if "convert" not in op.name and "convert" not in callee:
                    continue
            om = _ONE_SHAPE.search(op.shape_str)
            if om is None or om.group(1) != "f32":
                continue
            out_numel = _numel(om)
            pm = _CALL_PARENS.match(op.line)
            if pm is None:
                continue
            for em in _OPERAND_ENTRY.finditer(pm.group(1)):
                # inline operand type, else the operand's defining op
                sm = _ONE_SHAPE.search(em.group(1) or
                                       comp.shapes.get(em.group(2), ""))
                if sm and sm.group(1) == "bf16" and _numel(sm) == out_numel:
                    total += out_numel * 4
                    break
    return total


def profile(hlo: str, default_group: int) -> Dict[str, float]:
    comps = parse_computations(hlo)
    entry = None
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            m = _COMP_HDR.match(line.strip())
            if m:
                entry = m.group(1)
                break
    if entry is None or entry not in comps:
        # fall back: computation with most ops
        entry = max(comps, key=lambda c: len(comps[c].ops))
    mult = _multiplicities(comps, entry)

    flops = 0.0
    dot_traffic = 0.0
    dot_count = 0.0
    sort_bytes = 0.0
    sort_count = 0.0
    coll_bytes: Dict[str, float] = defaultdict(float)
    coll_count: Dict[str, float] = defaultdict(float)
    max_trips = 1
    while_ops = 0.0
    for cname, comp in comps.items():
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        for op in comp.ops:
            if op.kind == "while":
                wm = _WHILE.search(op.line)
                if wm:
                    while_ops += m
                    max_trips = max(max_trips,
                                    _while_trips(op, comps, wm.group(1)))
            if op.kind == "sort":
                _, sz = _shape_info(op.shape_str)
                sort_bytes += m * sz
                sort_count += m
                continue
            if op.kind == "dot" or op.kind == "convolution":
                f, t = _dot_flops(comp, op)
                flops += m * f
                dot_traffic += m * t
                dot_count += m
                continue
            base_kind = op.kind.replace("-start", "")
            if base_kind in _COLL_KINDS:
                _, sz = _shape_info(op.shape_str)
                gm = _GROUPS_EXPLICIT.search(op.line)
                if gm:
                    n = len(gm.group(1).split(","))
                else:
                    gm = _GROUPS_IOTA.search(op.line)
                    n = int(gm.group(2)) if gm else default_group
                coll_bytes[base_kind] += m * sz * _coll_factor(base_kind, n)
                coll_count[base_kind] += m

    out = {"dot_flops": flops, "dot_traffic_bytes": dot_traffic,
           "dot_ops": dot_count, "max_while_trips": float(max_trips),
           "while_ops": while_ops,
           "cpu_upcast_bytes": float(_upcast_bytes_from_comps(comps)),
           "sort_bytes": sort_bytes, "sort_ops": sort_count,
           "collective_bytes": float(sum(coll_bytes.values())),
           "collective_ops": float(sum(coll_count.values()))}
    out.update({f"bytes.{k}": v for k, v in coll_bytes.items()})
    out.update({f"count.{k}": v for k, v in coll_count.items()})
    return out
