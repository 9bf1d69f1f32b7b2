"""Standalone dtype casts of parameter size in the round program.

A client step that casts its float32 params to the compute dtype outside
the passes that already read them moves the parameters once more: a
kernel that only reads one dtype and writes another. This module finds
such kernels in the round program's compiled HLO text, and reads their
device time from a traced run:

- a *cast* is an instruction that runs as a kernel of its own (it is not
  inside a fusion's computation) and whose output holds at least
  ``MIN_ELEMENTS`` elements, either a plain ``convert``, or a ``fusion``
  whose computation holds a ``convert`` and otherwise only what moves
  data without arithmetic: parameters, bitcasts, copies, transposes and
  the tuple of a multi-output fusion. A fusion that also does arithmetic
  (the optimizer's update, with the gradient's convert folded into it)
  is no cast;
- ``cast_ms`` is the self time (``scopes.self_ns``) of the casts that ran
  inside the round program's executions, ms per traced round on the
  busiest chip.

The HLO text is the one ``bench/scopes.py`` compiles for the name stacks,
compiled the same way; the trace's op events name the instruction.
"""
from __future__ import annotations

import math
import re
from typing import Optional

from bench import scopes
from bench import trace as tr

#: The smallest output counted: 2**20 elements, under every matrix leaf
#: of the cells' params (the smallest, fedlm-100m's 12 x 768 x 256, holds
#: 2.4M) and over their norm and bias leaves.
MIN_ELEMENTS = 2 ** 20
#: What a pure cast fusion may hold besides its converts.
MOVES = frozenset({"parameter", "convert", "bitcast", "copy", "transpose",
                   "tuple"})
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (.+?) ([\w\-]+)\(")
_ARRAY = re.compile(r"\b[a-z]+\d*\[([\d,]*)\]")
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")


def _elements(shape: str) -> int:
    """Elements of an HLO shape, summed over a tuple's arrays."""
    return sum(math.prod(int(d) for d in dims.split(",") if d)
               for dims in _ARRAY.findall(shape))


def computations(hlo_text: str) -> dict:
    """{computation: [(instruction, shape, opcode, fused computation or
    None)]} of an HLO module's text."""
    out, body = {}, None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            body = out.setdefault(m.group(1), [])
            continue
        m = _INSTRUCTION.match(line)
        if m and body is not None:
            calls = _CALLS.search(line) if m.group(3) == "fusion" else None
            body.append((m.group(1), m.group(2), m.group(3),
                         calls.group(1) if calls else None))
    return out


def casts(hlo_text: str) -> dict:
    """{computation: [instruction]} of the casts of each computation that
    runs kernels (every computation but the fusions' own)."""
    comps = computations(hlo_text)
    fused = {calls for body in comps.values() for *_, calls in body if calls}

    def pure(comp: str) -> bool:
        ops = {op for _, _, op, _ in comps.get(comp, ())}
        return "convert" in ops and ops <= MOVES

    return {name: [instr for instr, shape, op, calls in body
                   if _elements(shape) >= MIN_ELEMENTS
                   and (op == "convert" or (op == "fusion" and pure(calls)))]
            for name, body in comps.items() if name not in fused}


def cast_names(hlo_text: str) -> frozenset:
    """Every cast instruction of the module."""
    return frozenset(i for found in casts(hlo_text).values() for i in found)


def _workload(t: tr.Trace) -> Optional[str]:
    """The cell whose trace under ``scopes.TRACE_ROOT`` holds ``t``'s
    program executions (as ``scopes`` finds it)."""
    for f in scopes.TRACE_ROOT.glob("*/**/*.xplane.pb"):
        if scopes.program_marks(f)[0] == t.modules:
            return f.relative_to(scopes.TRACE_ROOT).parts[0]
    return None


def _names(ctx) -> Optional[frozenset]:
    """The cast instructions of the cell's compiled round program (None:
    no program execution in the trace, or no trace file of the run's)."""
    t = ctx["trace"]
    if not t.modules:
        return None

    def make():
        cell = _workload(t)
        return None if cell is None else cast_names(
            scopes.round_program_hlo(cell))
    return scopes._once(("casts", id(t)), t, make)


def cast_ms(ctx, names: Optional[frozenset] = None) -> Optional[float]:
    """Self time of the round program's casts (``names``: its cast
    instructions, by default read from its compiled HLO), in ms per traced
    round on the busiest chip; None where there is no program to read."""
    names = _names(ctx) if names is None else names
    if names is None:
        return None
    t, lo, hi = ctx["trace"], ctx["lo"], ctx["hi"]
    best = 0
    for plane, ops in t.ops.items():
        runs = [(s, e) for n, s, e in t.modules.get(plane, ())
                if n.startswith(ctx["round_module"])]
        kept = tr.clip(ops, lo, hi)
        best = max(best, sum(
            own for (name, s, e), own in zip(kept, scopes.self_ns(kept))
            if name.split(" = ")[0].lstrip("%") in names
            and any(r0 <= s and e <= r1 for r0, r1 in runs)))
    return best / ctx["rounds"] / 1e6
