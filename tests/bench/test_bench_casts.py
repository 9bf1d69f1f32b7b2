"""param_cast.device_ms: the round program's standalone casts of parameter
size, classified from its compiled HLO text and timed from the trace."""
from pathlib import Path

import bench_cells
import pytest

from bench import casts, scopes, spec

ROOT = bench_cells.ROOT
RECORDED = Path(__file__).parent / "data" / "trace_fedlm100m_fedpa_scoped.json"
SCOPE_METRICS = ("client_forward", "client_backward", "client_opt", "iasg",
                 "dp_delta", "aggregate_server")

# A local step's body as the parent's round program had it: the params'
# cast to bf16 as a fusion of its own, the embedding gradient's convert to
# f32, a small convert, and the optimizer's update (its gradient convert
# folded in, and the bf16 copy written beside the params); the entry casts
# the broadcast params once.
HLO = """\
HloModule jit_round_fn, entry_computation_layout={()->f32[]}

%fused_cast (param_0.1: f32[12,5,768,2048]) -> bf16[12,5,2048,768] {
  %param_0.1 = f32[12,5,768,2048]{3,2,1,0} parameter(0)
  %convert.1 = bf16[12,5,768,2048]{3,2,1,0} convert(f32[12,5,768,2048]{3,2,1,0} %param_0.1)
  %copy.1 = bf16[12,5,768,2048]{2,3,1,0} copy(bf16[12,5,768,2048]{3,2,1,0} %convert.1)
  ROOT %transpose.1 = bf16[12,5,2048,768]{3,2,1,0} transpose(bf16[12,5,768,2048]{2,3,1,0} %copy.1), dimensions={0,1,3,2}
}

%fused_update (param_0.2: f32[5,32768,768], param_1.2: bf16[5,32768,768]) -> (f32[5,32768,768], bf16[5,32768,768]) {
  %param_0.2 = f32[5,32768,768]{2,1,0} parameter(0)
  %param_1.2 = bf16[5,32768,768]{2,1,0} parameter(1)
  %convert.2 = f32[5,32768,768]{2,1,0} convert(bf16[5,32768,768]{2,1,0} %param_1.2)
  %add.2 = f32[5,32768,768]{2,1,0} add(f32[5,32768,768]{2,1,0} %param_0.2, f32[5,32768,768]{2,1,0} %convert.2)
  %convert.3 = bf16[5,32768,768]{2,1,0} convert(f32[5,32768,768]{2,1,0} %add.2)
  ROOT %tuple.2 = (f32[5,32768,768]{2,1,0}, bf16[5,32768,768]{2,1,0}) tuple(f32[5,32768,768]{2,1,0} %add.2, bf16[5,32768,768]{2,1,0} %convert.3)
}

%body (param.3: (s32[], f32[5,32768,768])) -> (s32[], f32[5,32768,768]) {
  %param.3 = (s32[], f32[5,32768,768]{2,1,0}) parameter(0)
  %convert_bitcast_fusion.23 = bf16[12,5,2048,768]{3,2,1,0} fusion(f32[12,5,768,2048]{3,2,1,0} %gte.1), kind=kLoop, calls=%fused_cast, metadata={op_name="jit(round_fn)/vmap()/while/body/closed_call/jvp(client_grad)/transpose"}
  %convert_element_type.1408 = f32[163840,768]{1,0} convert(bf16[163840,768]{1,0} %fusion.486), metadata={op_name="jit(round_fn)/vmap()/while/body/closed_call/transpose(jvp(client_grad))/convert_element_type"}
  %convert_element_type.9 = f32[5,768]{1,0} convert(bf16[5,768]{1,0} %gte.2)
  %fusion.513 = (f32[5,32768,768]{2,1,0}, bf16[5,32768,768]{2,1,0}) fusion(f32[5,32768,768]{2,1,0} %gte.3, bf16[5,32768,768]{2,1,0} %gte.4), kind=kLoop, calls=%fused_update, metadata={op_name="jit(round_fn)/vmap()/while/body/closed_call/client_opt/convert_element_type"}
  ROOT %tuple.4 = (s32[], f32[5,32768,768]{2,1,0}) tuple(s32[] %gte.5, f32[5,32768,768]{2,1,0} %gte.6)
}

ENTRY %main.80 (p.5: f32[32768,768]) -> f32[] {
  %p.5 = f32[32768,768]{1,0} parameter(0)
  %convert_element_type.233 = bf16[32768,768]{1,0} convert(f32[32768,768]{1,0} %p.5), metadata={op_name="jit(round_fn)/vmap()/convert_element_type"}
  %while.1 = (s32[], f32[5,32768,768]{2,1,0}) while((s32[], f32[5,32768,768]{2,1,0}) %tuple.5), condition=%cond, body=%body
  ROOT %constant.6 = f32[] constant(0)
}
"""

# One round: the step loop holds the three casts, a small convert and the
# update; after the loop the entry's cast; then the eval program, which
# reuses an instruction name.
TPU = "/device:TPU:0"
OPS = [("%while.1 = (s32[], f32[5,32768,768]) while()", 0, 100),
       ("%convert_bitcast_fusion.23 = bf16[12,5,2048,768] fusion()", 10, 25),
       ("%convert_element_type.1408 = f32[163840,768] convert()", 30, 40),
       ("%convert_element_type.9 = f32[5,768] convert()", 40, 42),
       ("%fusion.513 = (f32[5,32768,768], bf16[5,32768,768]) fusion()",
        50, 90),
       ("%convert_element_type.233 = bf16[32768,768] convert()", 110, 115),
       ("%convert_element_type.233 = bf16[32768,768] convert()", 160, 170)]
MODULES = {TPU: [("jit_round_fn(1)", 0, 150), ("jit__lambda(2)", 155, 175)]}


def _ctx(t, lo=0, hi=200, rounds=1):
    return {"trace": t, "lo": lo, "hi": hi, "rounds": rounds, "chips": 1,
            "round_module": "jit_round_fn"}


def _trace(ops=OPS):
    return scopes.ScopedTrace({TPU: ops}, MODULES, [("bench.round", 0, 200)],
                              {TPU: [""] * len(ops)})


def test_pure_casts_and_large_converts_are_counted():
    found = casts.casts(HLO)
    # the fusions' own computations run no kernel of their own
    assert sorted(found) == ["body", "main.80"]
    assert found["body"] == ["convert_bitcast_fusion.23",
                             "convert_element_type.1408"]
    assert found["main.80"] == ["convert_element_type.233"]
    assert casts.cast_names(HLO) == {"convert_bitcast_fusion.23",
                                     "convert_element_type.1408",
                                     "convert_element_type.233"}


@pytest.mark.parametrize("op,shape,elements", [
    ("add", "f32[1048576]", 2 ** 20),
    ("copy", "f32[1024,1024]", 2 ** 20),
    ("convert", "bf16[1024,1023]", 1024 * 1023),
])
def test_arithmetic_copies_and_small_converts_are_no_casts(op, shape,
                                                            elements):
    """A fusion that does arithmetic beside its convert, one that converts
    nothing, and a convert under 2**20 elements."""
    assert casts._elements(shape) == elements
    if op == "convert":
        text = (f"ENTRY %main (p: f32[]) -> f32[] {{\n"
                f"  %convert.1 = {shape}{{1,0}} convert(%p)\n}}\n")
    else:
        text = (f"%f (param_0: f32[1048576]) -> {shape} {{\n"
                f"  %param_0 = f32[1048576]{{0}} parameter(0)\n"
                f"  ROOT %{op}.1 = {shape}{{0}} {op}(%param_0)\n}}\n\n"
                f"ENTRY %main (p: f32[1048576]) -> {shape} {{\n"
                f"  ROOT %fusion.1 = {shape}{{0}} fusion(%p), kind=kLoop, "
                f"calls=%f\n}}\n")
    assert casts.cast_names(text) == frozenset()


def test_a_tuple_output_counts_its_arrays():
    assert casts._elements("(f32[1024,512]{1,0}, bf16[1024,512]{1,0})") \
        == 2 ** 20


def test_cast_time_per_round_inside_the_round_program():
    """Self time of the casts that ran inside the round program's
    executions; the eval program's op of the same name is left out."""
    names = casts.cast_names(HLO)
    assert casts.cast_ms(_ctx(_trace()), names) == pytest.approx(30e-6)
    assert casts.cast_ms(_ctx(_trace(), rounds=2), names) \
        == pytest.approx(15e-6)
    # a window that ends before the entry's cast
    assert casts.cast_ms(_ctx(_trace(), hi=100), names) \
        == pytest.approx(25e-6)


def test_the_reader_returns_nothing_without_a_program_to_read(tmp_path,
                                                              monkeypatch):
    """No program execution in the trace, or no trace file of the run's
    to compile its program from (a recorded trace)."""
    monkeypatch.setattr(scopes, "TRACE_ROOT", tmp_path)
    read = spec.metric_reader(ROOT, "param_cast.device_ms")
    assert read(_ctx(scopes.ScopedTrace({TPU: OPS}, {}, [], {TPU: []}))) \
        is None
    assert read(_ctx(_trace())) is None


def test_scope_readings_are_unchanged_by_the_cast_reader():
    """The scope metrics read a recorded chip trace the same before and
    after the cast reader has read it."""
    t = scopes.ScopedTrace.from_json(RECORDED.read_text())
    rounds = [s for s in t.spans if s[0] == "bench.round"]
    ctx = {"trace": t, "lo": min(s[1] for s in rounds),
           "hi": max(s[2] for s in rounds), "rounds": len(rounds),
           "chips": 1, "round_module": "jit_round_fn"}

    def scope_readings():
        return {m: spec.metric_reader(ROOT, f"{m}.device_ms")(ctx)
                for m in SCOPE_METRICS}

    before = scope_readings()
    # the recorded program's convert kernels, as the classifier would
    # name most of them
    names = frozenset(op.split(" = ")[0].lstrip("%")
                      for op, _, _ in t.ops[TPU]
                      if op.startswith("%convert_"))
    cast = casts.cast_ms(ctx, names)
    assert 0 < cast < spec.metric_reader(ROOT, "round_program.device_ms")(ctx)
    assert scope_readings() == before
