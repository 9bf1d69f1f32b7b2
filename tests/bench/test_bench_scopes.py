"""Per-scope self time, the program's host spans, and their readers."""
from pathlib import Path

import bench_cells
import pytest

from bench import scopes, spec
from bench import trace as tr

ROOT = bench_cells.ROOT
RECORDED = Path(__file__).parent / "data" / "trace_fedlm100m_fedpa_scoped.json"
OLD = Path(__file__).parent / "data" / "trace_fedlm100m_fedpa.json"

DEVICE = ("client_forward", "client_backward", "client_opt", "iasg",
          "dp_delta", "aggregate_server")
HOST = ("cohort_wait", "dispatch", "on_round")

# one round of 200 ns: a while loop holds a forward and a backward op, the
# backward op holds an optimizer op; then one op of each other scope
P = "jit(round_fn)/vmap()/while/body/closed_call/"
OPS = [("while.1", 0, 100), ("fusion.2", 10, 40), ("fusion.3", 50, 90),
       ("fusion.4", 60, 70), ("fusion.5", 110, 130), ("fusion.6", 130, 160),
       ("fusion.7", 160, 170), ("fusion.8", 170, 180), ("fusion.9", 180, 190)]
STACKS = [P + "while", P + "jvp(client_grad)/dot_general",
          P + "transpose(jvp(client_grad))/checkpoint/rematted_computation/mul",
          P + "client_opt/add", P + "iasg_average/add",
          "dp_delta/cond/branch_1_fun/reduce_sum",
          "jit(round_fn)/vmap(dp_delta)/while/body/closed_call/dp_delta/mul",
          "jit(round_fn)/aggregate/dot_general",
          "jit(round_fn)/server_update/add"]
SMALL = scopes.ScopedTrace(
    ops={"/device:TPU:0": OPS},
    modules={"/device:TPU:0": [("jit_round_fn(1)", 0, 195)]},
    spans=[("bench.round", 0, 200), ("repro.round", 0, 199),
           ("repro.cohort_get", 1, 3), ("repro.dispatch", 3, 8),
           ("repro.eval", 8, 20), ("repro.sync", 9, 19),
           ("repro.on_round", 190, 199), ("repro.dispatch", 250, 260)],
    stacks={"/device:TPU:0": STACKS})


def _ctx(t, lo=0, hi=200, rounds=1):
    from bench import yardstick
    return {"trace": t, "lo": lo, "hi": hi, "rounds": rounds, "chips": 1,
            "peak": yardstick.peak("TPU v5 lite"),
            "flops_per_round": 1.97e6, "round_module": "jit_round_fn",
            "cohort_build_s": None}


def _read(metric, ctx):
    return spec.metric_reader(ROOT, metric)(ctx)


def test_self_time_leaves_out_nested_ops():
    ops = SMALL.ops["/device:TPU:0"]
    assert scopes.self_ns(ops) == [100 - 30 - 40, 30, 40 - 10, 10,
                                   20, 30, 10, 10, 10]
    # overlapping children count once; an op that ends past its
    # neighbour is not its child, and one inside both goes to the inner
    assert scopes.self_ns([("w", 0, 100), ("a", 10, 40), ("b", 30, 60),
                           ("c", 35, 50)]) == [100 - 50, 30, 30 - 15, 15]


@pytest.mark.parametrize("stack,scope", [
    (P + "jvp(client_grad)/dot_general", "client_grad"),
    (P + "transpose(jvp(client_grad))/while/body/add_any", "client_grad"),
    ("jit(round_fn)/vmap(dp_delta)/while/body/closed_call/dp_delta/cond",
     "dp_delta"),
    (P + "client_opt/add", "client_opt"),
    ("jit(round_fn)/server_update/aggregate/add", "aggregate"),
    ("jit(round_fn)/client_optimizer/add", None),
    (P + "transpose", None),
    ("", None)])
def test_innermost_scope_of_a_name_stack(stack, scope):
    assert scopes.scope_of(stack) == scope


@pytest.mark.parametrize("metric,ns", [
    ("client_forward.device_ms", 30), ("client_backward.device_ms", 30),
    ("client_opt.device_ms", 10), ("iasg.device_ms", 20),
    ("dp_delta.device_ms", 40), ("aggregate_server.device_ms", 20),
    ("engine.cohort_wait.host_ms", 2), ("engine.dispatch.host_ms", 5),
    ("engine.on_round.host_ms", 9)])
def test_readers_on_the_small_trace(metric, ns):
    assert _read(metric, _ctx(SMALL, rounds=1)) == pytest.approx(ns / 1e6)
    # per round: the same trace read as two rounds gives half (the host
    # spans count the engine's rounds, of which there is one)
    two = _read(metric, _ctx(SMALL, rounds=2))
    assert two == pytest.approx(ns / 1e6 / (2 if "device" in metric else 1))


def _metrics(kind):
    return [f"{m}.device_ms" for m in DEVICE] if kind == "device" else \
        [f"engine.{m}.host_ms" for m in HOST]


@pytest.mark.parametrize("kind", ["device", "host"])
def test_readers_return_nothing_without_the_programs_marks(kind, tmp_path,
                                                           monkeypatch):
    """A program that predates the marks: no name stacks, no repro.*
    spans, or no trace file of the run's to read them from."""
    monkeypatch.setattr(scopes, "TRACE_ROOT", tmp_path)
    bare = scopes.ScopedTrace(SMALL.ops, SMALL.modules,
                              [("bench.round", 0, 200)],
                              {"/device:TPU:0": [""] * len(OPS)})
    old = tr.Trace.from_json(OLD.read_text())
    for t in (bare, old, tr.Trace({}, {}, [])):
        for m in _metrics(kind):
            assert _read(m, _ctx(t)) is None, (m, t)


def test_scopes_a_round_does_not_run_read_zero():
    """FedAvg's round program marks its scopes but runs no IASG or DP."""
    fedavg = SMALL._replace(stacks={"/device:TPU:0": [
        "" if scopes.scope_of(st) in ("iasg_average", "dp_delta") else st
        for st in STACKS]})
    assert _read("iasg.device_ms", _ctx(fedavg)) == 0
    assert _read("dp_delta.device_ms", _ctx(fedavg)) == 0
    assert _read("client_opt.device_ms", _ctx(fedavg)) == pytest.approx(1e-5)


def test_scoped_trace_round_trips_through_json():
    assert scopes.ScopedTrace.from_json(SMALL.to_json()) == SMALL


def test_program_marks_keep_the_programs_host_spans(tmp_path):
    """A real ``.xplane.pb`` (of the CPU: no device planes) gives the
    ``repro.*`` spans, nested as written, and no ``bench.*`` one."""
    import jax

    from repro.core.spans import span

    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.round"):
        with span("round"), span("dispatch"):
            jax.numpy.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    modules, spans = scopes.program_marks(next(tmp_path.rglob("*.xplane.pb")))
    assert modules == {}
    at = {n: (s, e) for n, s, e in spans}
    assert sorted(at) == ["repro.dispatch", "repro.round"]
    assert at["repro.round"][0] <= at["repro.dispatch"][0] \
        < at["repro.dispatch"][1] <= at["repro.round"][1]


HLO = """\
ENTRY %main.9 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %while.1 = (s32[], f32[4]{0}) while(%tuple.3), condition=%c, body=%b, metadata={op_name="jit(round_fn)/vmap()/while" source_file="x.py"}
  %copy.2 = f32[4]{0} copy(f32[4]{0} %p)
  ROOT %fusion.4 = f32[4]{0} fusion(f32[4]{0} %copy.2), kind=kLoop, calls=%f, metadata={op_name="jit(round_fn)/iasg_average/add"}
}
"""


def test_op_names_of_compiled_hlo_text():
    assert scopes.op_names(HLO) == {
        "p": "", "while.1": "jit(round_fn)/vmap()/while", "copy.2": "",
        "fusion.4": "jit(round_fn)/iasg_average/add"}


def test_an_op_without_op_name_takes_the_stack_it_runs_inside():
    """Stacks join by instruction inside the round program's executions
    only: the eval program reuses instruction names."""
    names = {"while.1": "a/dp_delta/while", "copy.2": "", "fusion.4": "b"}
    ops = [("%while.1 = (s32[]) while()", 0, 100),
           ("%copy.2 = f32[4] copy()", 10, 20),        # inside the while
           ("%copy.2 = f32[4] copy()", 120, 130),      # outside any op
           ("%fusion.4 = f32[4] fusion()", 140, 150),
           ("%fusion.4 = f32[4] fusion()", 210, 220)]  # the eval program
    modules = [("jit_round_fn(7)", 0, 200), ("jit__lambda(8)", 205, 230)]
    assert scopes.stacks_of(ops, modules, names, "jit_round_fn") == [
        "a/dp_delta/while", "a/dp_delta/while", "", "b", ""]


def test_readers_on_a_recorded_chip_trace():
    """Two rounds of a traced fedlm100m-fedpa window on one TPU v5e, with
    the round program's name stacks (joined from its compiled HLO) and the
    round loop's spans; ops shorter than 50 us are left out of the file."""
    t = scopes.ScopedTrace.from_json(RECORDED.read_text())
    rounds = [s for s in t.spans if s[0] == "bench.round"]
    ctx = _ctx(t, min(s[1] for s in rounds), max(s[2] for s in rounds),
               len(rounds))
    program = _read("round_program.device_ms", ctx)
    device = {m: _read(f"{m}.device_ms", ctx) for m in DEVICE}
    assert all(v > 0 for v in device.values()), device
    # the scopes hold most of the round program, and no more than it
    assert 0.9 * program <= sum(device.values()) <= program
    host = {m: _read(f"engine.{m}.host_ms", ctx) for m in HOST}
    assert all(v > 0 for v in host.values()), host
    # the device's idle gaps fall inside the round loop's own spans
    ops = t.ops["/device:TPU:0"]
    gaps = tr.idle_gaps(ops, ctx["lo"], ctx["hi"])
    longest = max(gaps, key=lambda g: g[1] - g[0])
    assert tr.label(longest, t.spans).startswith("repro.")
