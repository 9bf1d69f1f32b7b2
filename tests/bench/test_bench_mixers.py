"""The model mixers' device time: ops under ``slstm_scan`` / ``mlstm_chunk``."""
from pathlib import Path

import bench_cells
import pytest

from bench import mixers, scopes, spec
from bench import trace as tr

ROOT = bench_cells.ROOT
RECORDED = Path(__file__).parent / "data" / "trace_fedlm100m_fedpa_scoped.json"
OLD = Path(__file__).parent / "data" / "trace_fedlm100m_fedpa.json"
MIXERS = ("slstm.device_ms", "mlstm.device_ms")

# one round of 200 ns under client_grad: the forward's projection holds
# the sLSTM scan, whose while holds a step; the mLSTM scan, forward; the
# backward's rematerialised sLSTM scan and transposed mLSTM scan; an
# optimizer op outside both
G = "jit(round_fn)/vmap()/while/body/closed_call/"
F, B = G + "jvp(client_grad)/", G + "transpose(jvp(client_grad))/"
OPS = [("fusion.1", 0, 60), ("while.2", 10, 50), ("fusion.3", 20, 30),
       ("while.4", 60, 80), ("while.5", 80, 120), ("fusion.6", 90, 100),
       ("while.7", 120, 150), ("fusion.8", 150, 160), ("fusion.9", 170, 190)]
STACKS = [F + "dot_general", F + "slstm_scan/while",
          F + "slstm_scan/while/body/tanh", F + "mlstm_chunk/while",
          B + "checkpoint/rematted_computation/slstm_scan/while",
          B + "checkpoint/rematted_computation/slstm_scan/while/body/mul",
          B + "transpose(jvp(mlstm_chunk))/while", B + "dot_general",
          G + "client_opt/add"]
SMALL = scopes.ScopedTrace(
    ops={"/device:TPU:0": OPS},
    modules={"/device:TPU:0": [("jit_round_fn(1)", 0, 195)]},
    spans=[("bench.round", 0, 200)], stacks={"/device:TPU:0": STACKS})


def _ctx(t, lo=0, hi=200, rounds=1):
    return {"trace": t, "lo": lo, "hi": hi, "rounds": rounds, "chips": 1,
            "round_module": "jit_round_fn"}


def _read(metric, ctx):
    return spec.metric_reader(ROOT, metric)(ctx)


@pytest.mark.parametrize("stack,mixer", [
    (F + "slstm_scan/while", "slstm_scan"),
    (B + "transpose(jvp(mlstm_chunk))/while/body/add", "mlstm_chunk"),
    (B + "checkpoint/rematted_computation/slstm_scan/while", "slstm_scan"),
    (F + "slstm_scan_x/add", None),
    (F + "dot_general", None),
    ("", None)])
def test_mixer_of_a_name_stack(stack, mixer):
    assert mixers.mixer_of(stack) == mixer


@pytest.mark.parametrize("metric,ns", [
    # forward while 40 - step 10, step 10, backward while 40 - 10, step 10
    ("slstm.device_ms", 30 + 10 + 30 + 10),
    # forward 20, transposed 30
    ("mlstm.device_ms", 20 + 30)])
def test_readers_on_the_small_trace(metric, ns):
    assert _read(metric, _ctx(SMALL)) == pytest.approx(ns / 1e6)
    assert _read(metric, _ctx(SMALL, rounds=2)) == pytest.approx(ns / 2e6)


def test_mixers_lie_inside_the_client_steps():
    """The client step's scope readers count the mixers too."""
    ctx = _ctx(SMALL)
    steps = _read("client_forward.device_ms", ctx) \
        + _read("client_backward.device_ms", ctx)
    assert sum(_read(m, ctx) for m in MIXERS) <= steps


def test_readers_on_a_recorded_model_without_mixers():
    """fedlm-100m's traced FedPA rounds on one TPU v5e: marked, no mixer."""
    t = scopes.ScopedTrace.from_json(RECORDED.read_text())
    rounds = [s for s in t.spans if s[0] == "bench.round"]
    ctx = _ctx(t, min(s[1] for s in rounds), max(s[2] for s in rounds),
               len(rounds))
    assert [_read(m, ctx) for m in MIXERS] == [0, 0]


def test_readers_return_nothing_without_the_programs_marks(tmp_path,
                                                           monkeypatch):
    """A program that predates the marks: no name stacks, or no trace file
    of the run's to read them from."""
    monkeypatch.setattr(scopes, "TRACE_ROOT", tmp_path)
    bare = SMALL._replace(stacks={"/device:TPU:0": [""] * len(OPS)})
    old = tr.Trace.from_json(OLD.read_text())
    for t in (bare, old, tr.Trace({}, {}, [])):
        for m in MIXERS:
            assert _read(m, _ctx(t)) is None, (m, t)
