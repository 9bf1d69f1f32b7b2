"""The program's own marks in a traced run: device scopes and host spans.

``bench/trace.py`` reduces the trace to device-op and program intervals
and the benchmark's ``bench.*`` spans. This module keeps, beside them,
what the program marks itself (``repro.core.spans``):

- each round-program op's *name stack*, the HLO ``op_name`` of its
  ``jax.named_scope``s (``client_grad``, ``client_opt``, ``iasg_average``,
  ``dp_delta``, ``aggregate``, ``server_update``). The trace's op events
  name only the HLO instruction (``%fusion.12 = ...``), so the stack is
  joined from the round program's compiled HLO text, compiled again
  (from the compile cache) as ``harness.round_program_memory`` does. An
  op the compiler inserted without an ``op_name`` (a copy, the end of an
  async copy) takes the stack of the op it runs inside;
- the program's host spans, ``repro.*``, on the same clock.

A fused op carries the ``op_name`` of its fusion, which XLA takes from
the fusion's root: its whole time goes to that op's scope. Per-scope time
is *self time*: an op's duration minus the part of its interval that the
ops nested in it cover, so a ``while`` is not counted again with its body.

The readers under ``bench/metrics/`` call ``of(ctx)``. A program without
the marks (one that predates them) gives no scope and no ``repro.*``
span, and the readers then return None.
"""
from __future__ import annotations

import bisect
import functools
import json
import re
from pathlib import Path
from typing import NamedTuple, Optional

from bench import trace as tr

#: The program's device scopes, innermost wins where they nest.
SCOPES = ("client_grad", "client_opt", "iasg_average", "dp_delta",
          "aggregate", "server_update")
#: Where a scope sits in a name stack: a whole component, maybe wrapped in
#: transformations (``jvp(client_grad)``, ``transpose(jvp(client_grad))``).
_SCOPE = re.compile(r"(?:^|(?<=[/(;]))(" + "|".join(SCOPES) + r")(?=[/);]|$)")
#: AD marks the backward pass's ops with ``transpose(...)``.
BACKWARD = "transpose("
SPAN_PREFIX = "repro."
#: One instruction of an HLO module's text, and its ``op_name`` if any.
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = [^\n]*?(?:op_name=\"([^\"]*)\"[^\n]*)?$",
    re.M)
ROOT = Path(__file__).resolve().parents[1]
#: Where ``bench/harness.py`` keeps a traced run's trace until the
#: per-layer metrics are read (its ``TRACE_DIR``), one directory per cell.
TRACE_ROOT = ROOT / ".bench_trace"


class ScopedTrace(NamedTuple):
    """``bench.trace.Trace`` plus the program's marks (ns on one clock)."""

    ops: dict       # device plane -> [(name, start, end)]
    modules: dict   # device plane -> [(name, start, end)]
    spans: list     # [(name, start, end)]: bench.* and repro.* host spans
    stacks: dict    # device plane -> [name stack of each op in ops]

    def to_json(self) -> str:
        """Serialize (a recorded trace for the tests); each distinct stack
        is written once."""
        table = sorted({s for stacks in self.stacks.values() for s in stacks})
        index = {s: i for i, s in enumerate(table)}
        return json.dumps(dict(
            self._asdict(), stack_table=table,
            stacks={p: [index[s] for s in stacks]
                    for p, stacks in self.stacks.items()}))

    @staticmethod
    def from_json(text: str) -> "ScopedTrace":
        """Inverse of :meth:`to_json`."""
        d = json.loads(text)
        tup = lambda evs: [tuple(e) for e in evs]  # noqa: E731
        return ScopedTrace(
            {k: tup(v) for k, v in d["ops"].items()},
            {k: tup(v) for k, v in d["modules"].items()}, tup(d["spans"]),
            {k: [d["stack_table"][i] for i in v]
             for k, v in d["stacks"].items()})


def op_names(hlo_text: str) -> dict:
    """{HLO instruction: its ``op_name``} of a compiled module's text."""
    return {m.group(1): m.group(2) or ""
            for m in _INSTRUCTION.finditer(hlo_text)}


def parents(intervals) -> list:
    """For each interval, the index of the innermost other one that holds
    it (None: none does)."""
    order = sorted(range(len(intervals)),
                   key=lambda i: (intervals[i][1], -intervals[i][2]))
    out = [None] * len(intervals)
    open_ = []     # indices whose interval may still hold later ones
    for i in order:
        _, s, e = intervals[i]
        while open_ and intervals[open_[-1]][2] <= s:
            open_.pop()
        out[i] = next((j for j in reversed(open_)
                       if e <= intervals[j][2]), None)
        open_.append(i)
    return out


def self_ns(intervals) -> list:
    """Each interval's length less the union of the intervals directly
    nested in it (its children), in the order given."""
    children = {}
    for i, p in enumerate(parents(intervals)):
        if p is not None:
            children.setdefault(p, []).append(intervals[i])
    out = [e - s for _, s, e in intervals]
    for p, kids in children.items():
        out[p] -= sum(e - s for s, e in tr.merged(kids))
    return out


def stacks_of(ops, modules, names: dict, module: str) -> list:
    """The name stack of each op: its instruction's ``op_name`` in
    ``names`` where it runs inside an execution of program ``module``
    (else ""); without one, the stack of the op it runs inside."""
    runs = sorted((s, e) for n, s, e in modules if n.startswith(module))
    starts = [s for s, _ in runs]
    par = parents(ops)
    out = [""] * len(ops)
    for i in sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2])):
        name, s, e = ops[i]
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or e > runs[k][1]:
            continue
        own = names.get(name.split(" = ")[0].lstrip("%"), "")
        out[i] = own or ("" if par[i] is None else out[par[i]])
    return out


def program_marks(xplane: Path) -> tuple:
    """``(modules, spans)`` of one ``.xplane.pb``: each device plane's
    program executions (as ``bench.trace.load``) and the ``repro.*`` host
    spans."""
    from jax.profiler import ProfileData  # noqa: PLC0415

    modules, spans = {}, []
    for plane in ProfileData.from_file(str(xplane)).planes:
        for line in plane.lines:
            if tr.DEVICE_PLANE.match(plane.name) \
                    and line.name == tr.MODULES_LINE:
                modules.setdefault(plane.name, []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return modules, spans


def round_program_hlo(workload: str) -> str:
    """The compiled HLO text of cell ``workload``'s round program: its
    inputs built and the program lowered as ``harness.run`` does, its
    executable read from the compile cache."""
    import jax  # noqa: PLC0415

    from bench import harness, spec  # noqa: PLC0415
    from repro.core.sharded_round import make_fed_round  # noqa: PLC0415

    run_args = harness._program(spec.load_cell(ROOT, workload), 0)[0]
    args, cfg, fed, state, source = (run_args[0], run_args[1], run_args[2],
                                     run_args[4], run_args[-1])
    round_fn = make_fed_round(cfg, fed, placement="parallel",
                              q_chunk=harness._q_chunk(args),
                              compute_dtype=jax.numpy.dtype(
                                  args.compute_dtype))
    cohort = source.cohort(0)
    return jax.jit(round_fn).lower(state, cohort.batches, cohort.weights,
                                   cohort.survivors).compile().as_text()


def _scoped(t: tr.Trace, module: str) -> Optional[ScopedTrace]:
    """``t`` with the marks of the ``.xplane.pb`` under ``TRACE_ROOT``
    whose program executions are ``t``'s (None: no such file)."""
    if not t.modules:
        return None
    for f in TRACE_ROOT.glob("*/**/*.xplane.pb"):
        modules, spans = program_marks(f)
        if modules == t.modules:
            names = op_names(round_program_hlo(
                f.relative_to(TRACE_ROOT).parts[0]))
            return ScopedTrace(
                t.ops, t.modules, t.spans + spans,
                {p: stacks_of(ops, t.modules.get(p, []), names, module)
                 for p, ops in t.ops.items()})
    return None


#: id(trace) -> (trace, what was worked out from it once per run); the
#: trace is held so that its id stays its own
_CACHE: dict = {}


def _once(key, t, make):
    """``make()``, worked out once for ``key``."""
    if key not in _CACHE:
        _CACHE[key] = (t, make())
    return _CACHE[key][1]


def of(ctx) -> Optional[ScopedTrace]:
    """The traced run's ``ScopedTrace``: ``ctx["trace"]`` where it is one
    (a recorded trace), else ``ctx["trace"]`` with the marks of the run's
    own ``.xplane.pb``."""
    t = ctx["trace"]
    if isinstance(t, ScopedTrace):
        return t
    return _once(("marks", id(t)), t,
                 lambda: _scoped(t, ctx["round_module"]))


@functools.lru_cache(maxsize=4096)
def scope_of(stack: str) -> Optional[str]:
    """The innermost of ``SCOPES`` in a name stack (None: unscoped)."""
    found = _SCOPE.findall(stack)
    return found[-1] if found else None


def _split(t: ScopedTrace, lo: float, hi: float) -> dict:
    """{plane: {(scope, backward): self ns}} of the ops in [lo, hi)."""
    split = {}
    for plane, ops in t.ops.items():
        kept = [(op, stack) for op, stack in zip(ops, t.stacks[plane])
                if op[2] > lo and op[1] < hi]
        acc = split.setdefault(plane, {})
        for (_, stack), ns in zip(kept, self_ns(
                tr.clip([op for op, _ in kept], lo, hi))):
            k = (scope_of(stack), BACKWARD in stack)
            acc[k] = acc.get(k, 0) + ns
    return split


def scope_ms(ctx, *scopes: str, backward=None) -> Optional[float]:
    """Self time of the ops whose innermost scope is one of ``scopes``
    (and, where ``backward`` is given, whose name stack does or does not
    hold ``transpose(``), in ms per traced round on the busiest chip. 0
    where the program marks its scopes but ran none of these; None where
    no op carries a scope (a program without the marks)."""
    t = of(ctx)
    if t is None:
        return None
    lo, hi = ctx["lo"], ctx["hi"]
    split = _once(("split", id(t), lo, hi), t, lambda: _split(t, lo, hi))
    if not any(scope for acc in split.values() for scope, _ in acc):
        return None
    return max(sum(ns for (scope, back), ns in acc.items()
                   if scope in scopes and backward in (None, back))
               for acc in split.values()) / ctx["rounds"] / 1e6


def span_ms(ctx, name: str) -> Optional[float]:
    """Host ms of span ``repro.<name>`` per traced ``repro.round`` (the
    engine's round loop), over the rounds the trace holds whole."""
    t = of(ctx)
    if t is None:
        return None
    rounds = [(s, e) for n, s, e in t.spans if n == SPAN_PREFIX + "round"]
    if not rounds:
        return None
    ns = sum(e - s for n, s, e in t.spans if n == SPAN_PREFIX + name
             and any(r0 <= s and e <= r1 for r0, r1 in rounds))
    return ns / len(rounds) / 1e6
