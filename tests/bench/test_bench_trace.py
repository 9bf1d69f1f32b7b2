"""Trace reduction: interval union, idle gaps, module time, the readers."""
from pathlib import Path

import bench_cells
import pytest

from bench import spec
from bench import trace as tr

ROOT = bench_cells.ROOT
RECORDED = Path(__file__).parent / "data" / "trace_fedlm100m_fedpa.json"

# two rounds of 100 ns on one chip; ops overlap inside round 1
SMALL = tr.Trace(
    ops={"/device:TPU:0": [("fusion.1", 10, 40), ("fusion.2", 30, 60),
                           ("copy.3", 70, 90), ("fusion.1", 120, 180)]},
    modules={"/device:TPU:0": [("jit_round_fn(1)", 10, 90),
                               ("jit__lambda(2)", 92, 95),
                               ("jit_round_fn(1)", 120, 185)]},
    spans=[("bench.round", 0, 100), ("bench.round", 100, 200),
           ("bench.eval", 60, 68), ("bench.boundary", 100, 110)])


def test_busy_is_the_union_of_op_intervals():
    assert tr.busy_ns(SMALL.ops["/device:TPU:0"], 0, 200) == 50 + 20 + 60
    assert tr.busy_ns(SMALL.ops["/device:TPU:0"], 35, 75) == 25 + 5


def test_idle_gaps_and_their_labels():
    gaps = tr.idle_gaps(SMALL.ops["/device:TPU:0"], 0, 200)
    assert gaps == [(0, 10), (60, 70), (90, 120), (180, 200)]
    assert [tr.label(g, SMALL.spans) for g in gaps] == [
        "bench.round", "bench.eval", "bench.boundary", "bench.round"]
    assert tr.label((250, 260), SMALL.spans) == "outside any span"


def test_module_time_by_program_name():
    mods = SMALL.modules["/device:TPU:0"]
    assert tr.module_ns(mods, "jit_round_fn", 0, 200) == 80 + 65
    assert tr.module_ns(mods, "jit_round_fn", 0, 100) == 80


def test_trace_round_trips_through_json():
    assert tr.Trace.from_json(SMALL.to_json()) == SMALL


def _ctx(t, lo, hi, rounds):
    from bench import yardstick
    return {"trace": t, "lo": lo, "hi": hi, "rounds": rounds, "chips": 1,
            "peak": yardstick.peak("TPU v5 lite"),
            "flops_per_round": 1.97e6, "round_module": "jit_round_fn",
            "cohort_build_s": [0.002, 0.004]}


def test_readers_on_the_small_trace():
    ctx = _ctx(SMALL, 0, 200, 2)
    read = lambda m: spec.metric_reader(ROOT, m)(ctx)  # noqa: E731
    assert read("device.idle_share") == pytest.approx(100 * (1 - 130 / 200))
    assert read("round_program.device_ms") == pytest.approx(145 / 2 / 1e6)
    # 2 rounds of 1.97e6 flops in 200 ns against 197e12 flop/s
    assert read("mfu") == pytest.approx(100 * 2 * 1.97e6 / 200e-9 / 197e12)
    assert read("cohort_build.host_ms") == pytest.approx(3.0)


def test_readers_return_nothing_when_there_is_nothing_to_read():
    empty = tr.Trace({}, {}, [])
    ctx = dict(_ctx(empty, 0, 200, 2), cohort_build_s=None)
    for m in ("device.idle_share", "round_program.device_ms",
              "cohort_build.host_ms"):
        assert spec.metric_reader(ROOT, m)(ctx) is None


def test_reduction_of_a_recorded_chip_trace():
    """16 ms of a traced fedlm100m-fedpa window on one TPU v5e, around a
    round boundary: the end of one round program, the eval program, the
    host's boundary, and the start of the next round program."""
    t = tr.Trace.from_json(RECORDED.read_text())
    rounds = [s for s in t.spans if s[0] == "bench.round"]
    lo, hi = min(s[1] for s in rounds), max(s[2] for s in rounds)
    assert hi - lo == 16_000_000
    ops = t.ops["/device:TPU:0"]
    busy = tr.busy_ns(ops, lo, hi)
    gaps = tr.idle_gaps(ops, lo, hi)
    assert busy == 10_255_596
    assert busy + sum(e - s for s, e in gaps) == hi - lo
    mods = t.modules["/device:TPU:0"]
    assert tr.module_ns(mods, "jit_round_fn", lo, hi) == 8_682_180
    # the device waits 5.7 ms between the eval and the next round program
    longest = max(gaps, key=lambda g: g[1] - g[0])
    assert longest[1] - longest[0] == 5_736_970
    assert tr.label(longest, t.spans) == "bench.round"
