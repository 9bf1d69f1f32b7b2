"""Reduction of a profiler trace to intervals, and of intervals to numbers.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
``(name, start_ns, end_ns)`` intervals: the device operations and the
executions of whole jitted programs on each chip (the ``XLA Ops`` and
``XLA Modules`` lines of each ``/device:TPU:<n>`` plane), and the
benchmark's own host spans (``bench.*``). Everything after ``load`` works
on those intervals alone, so it can be checked on a small recorded trace.
"""
from __future__ import annotations

import json
import re
from pathlib import Path
from typing import NamedTuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."


class Trace(NamedTuple):
    """Intervals of one traced window (ns on the trace's clock)."""

    ops: dict       # device plane -> [(name, start, end)]
    modules: dict   # device plane -> [(name, start, end)]
    spans: list     # [(name, start, end)] host spans of the benchmark

    def to_json(self) -> str:
        """Serialize (a recorded trace for the tests)."""
        return json.dumps(self._asdict())

    @staticmethod
    def from_json(text: str) -> "Trace":
        """Inverse of :meth:`to_json`."""
        d = json.loads(text)
        tup = lambda evs: [tuple(e) for e in evs]  # noqa: E731
        return Trace({k: tup(v) for k, v in d["ops"].items()},
                     {k: tup(v) for k, v in d["modules"].items()},
                     tup(d["spans"]))


def load(trace_dir: Path) -> Trace:
    """Read the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData  # noqa: PLC0415

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {len(files)}")
    prof = ProfileData.from_file(str(files[0]))
    ops, modules, spans = {}, {}, []
    for plane in prof.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    dest = ops if line.name == OPS_LINE else modules
                    dest.setdefault(plane.name, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops, modules, spans)


def clip(intervals, lo: float, hi: float) -> list:
    """Intervals cut to the window [lo, hi); those outside it dropped."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in intervals
            if e > lo and s < hi]


def merged(intervals) -> list:
    """The union of intervals as sorted disjoint (start, end) pairs."""
    out = []
    for _, s, e in sorted(intervals, key=lambda t: t[1]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(p) for p in out]


def busy_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of intervals inside [lo, hi)."""
    return sum(e - s for s, e in merged(clip(intervals, lo, hi)))


def idle_gaps(intervals, lo: float, hi: float) -> list:
    """(start, end) stretches of [lo, hi) that no interval covers."""
    gaps, t = [], lo
    for s, e in merged(clip(intervals, lo, hi)):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < hi:
        gaps.append((t, hi))
    return gaps


def time_by_name(intervals, lo: float, hi: float) -> dict:
    """{name: summed ns} of intervals inside [lo, hi)."""
    out = {}
    for n, s, e in clip(intervals, lo, hi):
        out[n] = out.get(n, 0.0) + (e - s)
    return out


def module_ns(modules, prefix: str, lo: float, hi: float) -> float:
    """Summed time of executions of programs whose name starts with
    ``prefix`` (a jitted function ``f`` runs as module ``jit_f...``)."""
    return sum(t for n, t in time_by_name(modules, lo, hi).items()
               if n.startswith(prefix))


def label(gap, spans) -> str:
    """The innermost benchmark span covering the middle of ``gap``."""
    mid = (gap[0] + gap[1]) / 2
    covering = [(e - s, n) for n, s, e in spans if s <= mid < e]
    return min(covering)[1] if covering else "outside any span"
