"""Trip-count-aware cost analysis of optimized (post-SPMD) HLO text.

XLA's ``compiled.cost_analysis()`` visits every computation exactly ONCE —
``while`` bodies are NOT multiplied by their trip counts (verified
empirically in EXPERIMENTS.md §Roofline/Methodology: a 7-iteration scanned
matmul reports 1x the matmul flops). Since the whole framework leans on
``lax.scan`` (over layers, local steps, clients, MoE chunks) precisely to
keep compile time depth-independent, the built-in numbers undercount by
orders of magnitude.

This module re-derives per-device cost from the compiled module text:

  1. parse computations and their instructions;
  2. build an execution-multiplier per computation by propagating
     ``while`` trip counts (recovered from counter-vs-constant conditions,
     the lax.scan pattern) and fusion/call/reduce edges through the call
     graph — nested loops multiply;
  3. FLOPs: 2 * numel(result) * contracted_size for every ``dot`` (+
     convolution treated via output x kernel numel), scaled by multiplier;
  4. bytes: sum of (result + operand) buffer bytes per materializing
     instruction, scaled — the post-fusion instruction granularity is a
     good proxy for HBM traffic;
  5. collective bytes per kind, with the same multipliers (superseding the
     single-level scaling in ``collectives.py``).

All numbers are per device: the post-partitioning module is the per-device
program.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional, Tuple


_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
    "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1, "f8e5m2fnuz": 1,
    "f8e4m3fnuz": 1, "f8e3m4": 1, "f8e8m0fnu": 1, "f4e2m1fn": 1,
}

COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                    "all-to-all", "collective-permute")

# header params may contain nested parens (tuple-typed params), so match
# loosely up to the arrow
_COMP_HDR = re.compile(
    r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*\S.*\{\s*$")
# tuple shapes may contain /*index=N*/ comments — match to the closing paren
_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*((?:\([^)]*\))|(?:\S+))\s+"
    r"([\w\-]+)\((.*)$")
_ARRAY = re.compile(r"(\w+)\[([\d,]*)\]")


class Instr(NamedTuple):
    """One parsed HLO instruction: name, result shape, opcode, operand text."""

    name: str
    shape: str
    op: str
    rest: str


def _shape_numel_bytes(shape_str: str) -> Tuple[int, int]:
    numel = 0
    total = 0
    for dtype, dims in _ARRAY.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        numel += n
        total += n * _DTYPE_BYTES[dtype]
    return numel, total


def parse_module(hlo: str) -> Dict[str, List[Instr]]:
    """Split HLO text into computations, each a list of parsed Instrs."""
    comps: Dict[str, List[Instr]] = {}
    name: Optional[str] = None
    entry: Optional[str] = None
    for line in hlo.splitlines():
        m = _COMP_HDR.match(line)
        if m:
            name = m.group(1)
            comps[name] = []
            if line.lstrip().startswith("ENTRY"):
                entry = name
            continue
        if name is None:
            continue
        mi = _INSTR.match(line)
        if mi:
            comps[name].append(Instr(*mi.groups()))
    comps["__entry__"] = comps.get(entry, [])
    if entry:
        comps["__entry_name__"] = entry  # type: ignore[assignment]
    return comps


def _trip_count(cond_instrs: List[Instr]) -> int:
    """lax.scan conditions compare the counter against a constant."""
    best = 1
    for ins in cond_instrs:
        if ins.op == "constant" and ins.shape.startswith(("s32[]", "s64[]",
                                                          "u32[]", "u64[]")):
            m = re.match(r"(\d+)", ins.rest)
            if m:
                best = max(best, int(m.group(1)))
    return best


def _callees(ins: Instr) -> List[str]:
    """Computations this instruction invokes (fusion/call/while/etc.)."""
    out = []
    for key in ("calls=", "to_apply=", "condition=", "body=", "branch_computations="):
        for m in re.finditer(re.escape(key) + r"\{?%?([\w.\-,% {}]+)", ins.rest):
            blob = m.group(1)
            for nm in re.split(r"[,\s{}%]+", blob):
                if nm:
                    out.append(nm)
            break
    return out


def fusion_called(comps: Dict[str, List[Instr]]) -> set:
    """Computations inlined into fusions / reducers: their internal
    intermediates live in registers/VMEM, not HBM — flops count, bytes
    don't."""
    out = set()
    for cname, instrs in comps.items():
        if cname.startswith("__"):
            continue
        for ins in instrs:
            if ins.op in ("fusion", "reduce", "reduce-window", "map", "sort",
                          "scatter", "select-and-scatter", "all-reduce",
                          "reduce-scatter", "custom-call"):
                out.update(_callees(ins))
    return out


def multipliers(comps: Dict[str, List[Instr]]) -> Dict[str, float]:
    """Execution count per computation (entry = 1; while bodies x trips)."""
    entry = comps.get("__entry_name__")
    mult: Dict[str, float] = defaultdict(float)
    if not entry:
        return mult
    mult[entry] = 1.0
    # topological-ish fixpoint (call graph is a DAG; few iterations suffice)
    for _ in range(50):
        new = defaultdict(float)
        new[entry] = 1.0
        for cname, instrs in comps.items():
            if cname.startswith("__"):
                continue
            m = mult.get(cname, 0.0)
            if m == 0.0:
                continue
            for ins in instrs:
                if ins.op == "while":
                    mcond = re.search(r"condition=%?([\w.\-]+)", ins.rest)
                    mbody = re.search(r"body=%?([\w.\-]+)", ins.rest)
                    if mcond and mbody:
                        trips = _trip_count(comps.get(mcond.group(1), []))
                        new[mbody.group(1)] += m * trips
                        new[mcond.group(1)] += m * (trips + 1)
                elif ins.op in ("fusion", "call", "conditional", "map",
                                "reduce", "reduce-window", "sort", "scatter",
                                "select-and-scatter", "all-reduce",
                                "reduce-scatter", "custom-call"):
                    for callee in _callees(ins):
                        if callee in comps:
                            new[callee] += m
        if dict(new) == dict(mult):
            break
        mult = new
    return mult


def _dot_flops(ins: Instr, shapes: Dict[str, str]) -> float:
    out_numel, _ = _shape_numel_bytes(ins.shape)
    m = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", ins.rest)
    operands = re.findall(r"%([\w.\-]+)", ins.rest.split(",  ")[0])
    contracted = 1
    if m and operands:
        lhs_shape = shapes.get(operands[0], "")
        arr = _ARRAY.search(lhs_shape)
        if arr:
            dims = [int(x) for x in arr.group(2).split(",") if x]
            for ci in m.group(1).split(","):
                if ci and int(ci) < len(dims):
                    contracted *= dims[int(ci)]
    return 2.0 * out_numel * contracted


_SKIP_BYTES_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
                   "bitcast", "after-all", "partition-id", "replica-id",
                   "iota", "while", "conditional", "call"}


def _operand_names(ins: Instr) -> List[str]:
    """Operand instruction names: the %refs before the first unparenthesized
    option key (operand list ends at the matching close paren)."""
    head = ins.rest.split("), ")[0]
    return re.findall(r"%([\w.\-]+)", head)


def _param_instr_name(pidx: int, callee: List[Instr]) -> Optional[str]:
    """Name of the callee's ``pidx``-th parameter instruction, if present."""
    for ins in callee:
        if ins.op == "parameter" and ins.rest.startswith(f"{pidx})"):
            return ins.name
    return None


def _alias_chain(pname: str, callee: List[Instr]) -> set:
    """Names reachable from ``pname`` through same-size alias ops
    (bitcast/reshape/copy/convert/transpose): a scan body often bitcasts
    the stacked buffer before slicing it."""
    aliases = {pname}
    for _ in range(4):
        grew = False
        for ins in callee:
            if ins.op in ("bitcast", "reshape", "copy", "convert",
                          "transpose") and ins.name not in aliases:
                if aliases & set(_operand_names(ins)):
                    aliases.add(ins.name)
                    grew = True
        if not grew:
            break
    return aliases


def _dus_update_bytes(ops_: List[str], callee: List[Instr]) -> float:
    """Update-extent bytes of a dynamic-update-slice's second operand."""
    if len(ops_) >= 2:
        for cand in callee:
            if cand.name == ops_[1]:
                _, ub = _shape_numel_bytes(cand.shape)
                return ub
    return 0.0


def _param_read_bytes(pidx: int, full_bytes: float,
                      callee: List[Instr]) -> float:
    """Bytes a fused computation actually reads of its ``pidx``-th parameter.

    Scan bodies receive whole stacked arrays and dynamic-slice one step's
    worth inside the fusion; charging the full operand per iteration
    overcounted memory traffic ~1000x. If every use of the parameter is a
    slicing op, charge the slice sizes; otherwise the full buffer.
    """
    pname = _param_instr_name(pidx, callee)
    if pname is None:
        return full_bytes
    aliases = _alias_chain(pname, callee)
    read = 0.0
    for ins in callee:
        if ins.op == "parameter" or ins.name in aliases:
            continue
        ops_ = _operand_names(ins)
        if not (aliases & set(ops_)):
            continue
        if ins.op in ("dynamic-slice", "slice", "gather"):
            _, rb = _shape_numel_bytes(ins.shape)
            read += rb
        elif ins.op == "dynamic-update-slice" and ops_ and ops_[0] in aliases:
            # in-place update of the buffer: reads ~the update extent
            ub = _dus_update_bytes(ops_, callee)
            read += ub if ub else full_bytes
        else:
            return full_bytes
    return min(read, full_bytes)


def _fusion_result_bytes(ins: Instr, callee: List[Instr]) -> float:
    """Result-write bytes of a fusion: a dynamic-update-slice root writes
    only the update extent even though the result shape is the full buffer
    (XLA aliases it in place)."""
    _, rb = _shape_numel_bytes(ins.shape)
    if not callee:
        return rb
    root = callee[-1]
    if root.op == "dynamic-update-slice":
        ops_ = _operand_names(root)
        if len(ops_) >= 2:
            for cand in callee:
                if cand.name == ops_[1]:
                    _, ub = _shape_numel_bytes(cand.shape)
                    return min(2.0 * ub, rb)  # read-modify-write of the slice
    return rb


def _instr_bytes(ins: Instr, shapes: Dict[str, str],
                 comps: Optional[Dict[str, List[Instr]]] = None) -> float:
    """HBM traffic estimate for one instruction execution.

    Slicing ops read/write only the slice, never the backing buffer —
    charging full operands would bill a scan's stacked input once per
    iteration (1000x overcounts observed before this special-casing).
    """
    _, rb = _shape_numel_bytes(ins.shape)
    if ins.op in ("dynamic-slice", "slice", "gather"):
        return 2.0 * rb
    if ins.op in ("dynamic-update-slice", "scatter"):
        ops_ = _operand_names(ins)
        ub = 0.0
        if len(ops_) >= 2 and ops_[1] in shapes:
            _, ub = _shape_numel_bytes(shapes[ops_[1]])
        return 3.0 * ub if ub else 2.0 * rb
    callee = None
    if ins.op == "fusion" and comps is not None:
        m = re.search(r"calls=%?([\w.\-]+)", ins.rest)
        if m:
            callee = comps.get(m.group(1))
    if callee is not None:
        rb = _fusion_result_bytes(ins, callee)
    ob = 0.0
    for i, opn in enumerate(_operand_names(ins)):
        if opn in shapes:
            _, b = _shape_numel_bytes(shapes[opn])
            if callee is not None:
                b = _param_read_bytes(i, b, callee)
            ob += b
    return rb + ob


def analyze(hlo: str) -> Dict[str, object]:
    """Returns {"flops", "bytes", "collectives": {...}, "dots": int}."""
    comps = parse_module(hlo)
    mult = multipliers(comps)
    fused = fusion_called(comps)
    flops = 0.0
    bts = 0.0
    ndots = 0
    coll = {k: {"bytes": 0.0, "count": 0.0} for k in COLLECTIVE_KINDS}

    for cname, instrs in comps.items():
        if cname.startswith("__"):
            continue
        m = mult.get(cname, 0.0)
        if m == 0.0:
            continue
        shapes = {i.name: i.shape for i in instrs}
        in_fusion = cname in fused
        for ins in instrs:
            if ins.op == "dot":
                flops += m * _dot_flops(ins, shapes)
                ndots += 1
            if ins.op == "convolution":
                out_numel, _ = _shape_numel_bytes(ins.shape)
                flops += m * 2.0 * out_numel  # lower bound; CNNs not on the hot path
            base_op = ins.op
            for kind in COLLECTIVE_KINDS:
                if base_op == kind or base_op == kind + "-start":
                    _, rb = _shape_numel_bytes(ins.shape)
                    coll[kind]["bytes"] += m * rb
                    coll[kind]["count"] += m
            if in_fusion or base_op in _SKIP_BYTES_OPS \
                    or base_op.endswith("-done"):
                continue
            bts += m * _instr_bytes(ins, shapes, comps)

    out = {
        "flops": flops,
        "bytes": bts,
        "dots": ndots,
        "collectives": {k: v for k, v in coll.items()},
    }
    out["collectives"]["total_bytes"] = sum(
        v["bytes"] for v in coll.values())
    return out
