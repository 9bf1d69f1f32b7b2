"""Host spans of the round loop, on the profiler's clock, with counters.

``span(name)`` marks one stretch of host work as ``repro.<name>``. Under
an active ``jax.profiler`` trace it is a host span on the device trace's
clock, so an idle gap of the device can be put down to what the host was
doing. Always, traced or not, it adds one call and its
``time.perf_counter_ns`` duration to a process-wide counter, which
``counters()`` snapshots: the difference of two snapshots is what each
phase took between them, over stretches no trace covers.

The device side of the round is marked with ``jax.named_scope`` where
the work happens (``client_grad``, ``client_opt``, ``iasg_average``,
``dp_delta``, ``aggregate``, ``server_update``): metadata of the compiled
ops, at no cost when the program runs.
"""
from __future__ import annotations

import contextlib
import time

import jax

#: name -> [calls, summed ns], since the process started
_COUNTERS: dict = {}


@contextlib.contextmanager
def span(name: str):
    """Time the ``with`` body as host span ``repro.<name>``."""
    t0 = time.perf_counter_ns()
    try:
        with jax.profiler.TraceAnnotation(f"repro.{name}"):
            yield
    finally:
        count = _COUNTERS.setdefault(name, [0, 0])
        count[0] += 1
        count[1] += time.perf_counter_ns() - t0


def counters() -> dict:
    """A snapshot: ``{name: [calls, summed ns]}``."""
    return {name: list(count) for name, count in _COUNTERS.items()}
