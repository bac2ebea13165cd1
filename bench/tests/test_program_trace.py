"""The reduction of the program's spans and scopes, on small synthetic
traces (times in ns), and the scope readers on the reduced cell's step."""
import pytest

from bench import harness
from bench import program_trace as pt
from bench import trace as tr
from bench.metrics import _scopes
from bench.tests import cells

SCOPE_READERS = ["fwd_bwd_device_ms", "gossip_device_ms"]
# two steps of the loop and the start of a third, outside [0, 200)
SPANS = [("train.step", 0, 100), ("train.batch.generate", 0, 10),
         ("train.batch.place", 10, 2), ("train.dispatch", 12, 2),
         ("train.host_read", 14, 81),
         ("train.step", 100, 100), ("train.batch.generate", 100, 12),
         ("train.batch.place", 112, 1), ("train.dispatch", 113, 3),
         ("train.host_read", 116, 80), ("train.host_read", 196, 3),
         ("train.step", 200, 50), ("train.batch.generate", 200, 9)]


def test_the_spans_of_the_steps_in_the_window_summed_and_counted():
    got = pt.per_step(SPANS, 0, 200)
    assert got == {"train.step": (2, 200), "train.batch.generate": (2, 22),
                   "train.batch.place": (2, 3), "train.dispatch": (2, 5),
                   "train.host_read": (3, 164)}
    assert [s[1] for s in pt.steps_in(SPANS, 0, 200)] == [0, 100]
    assert pt.per_step(SPANS, 100, 101)["train.host_read"] == (2, 83)


def test_step_self_time_with_nested_and_overlapping_children():
    step = ("train.step", 0, 100)
    kids = [("a", 10, 20), ("a.inner", 15, 5), ("b", 25, 15),
            ("c", 90, 20)]            # c runs on past the step's end
    # covered: [10, 40) and [90, 100)
    assert pt.self_ns(step, kids) == 60
    assert pt.self_ns(step, []) == 100
    s0 = pt.steps_in(SPANS, 0, 200)[0]
    assert pt.self_ns(s0, pt.children(SPANS, s0)) == 5


@pytest.mark.parametrize("path, fwd_bwd, gossip", [
    ("jit(pipelined_step)/step.fwd_bwd/vmap(jvp(loss_of_rows))/dot_general",
     True, False),
    ("jit(pipelined_step)/step.fwd_bwd/vmap(transpose(jvp(step.fwd_bwd)))"
     "/vmap(jvp())/checkpoint/mul", True, False),
    ("jit(step)/vmap(transpose(jvp(step.fwd_bwd)))/while/body/add_any",
     True, False),
    ("jit(pipelined_step)/step.gossip/shard_map/"
     "jit(gossip_apply_w_resident_pallas)/gossip_apply_w_resident_pallas/"
     "pallas_call", False, True),
    ("jit(pipelined_step)/div", False, False),
    ("jit(step)/step.fwd_bwd_extra/mul", False, False),
    ("", False, False),
])
def test_scopes_match_as_components_through_transformations(path, fwd_bwd,
                                                             gossip):
    assert pt.in_scope(path, "step.fwd_bwd") == fwd_bwd
    assert pt.in_scope(path, "step.gossip") == gossip


def test_scope_time_is_the_union_of_its_ops_in_the_window():
    scopes = {"fusion.1": "jit(s)/step.fwd_bwd/vmap(jvp(f))/dot_general",
              "fusion.2": "jit(s)/vmap(transpose(jvp(step.fwd_bwd)))/mul",
              "gossip_apply_w_resident_pallas.1": "jit(s)/step.gossip/pc",
              "copy.1": "jit(s)/div"}
    ops = [("fusion.1", 0, 10), ("fusion.2", 5, 10), ("copy.1", 15, 5),
           ("gossip_apply_w_resident_pallas.1", 20, 10), ("fusion.9", 30, 5),
           ("fusion.1", 90, 20)]
    assert pt.scope_ns(ops, scopes, "step.fwd_bwd", 0, 100) == 15 + 10
    assert pt.scope_ns(ops, scopes, "step.gossip", 0, 100) == 10
    assert pt.scope_ns(ops, scopes, "step.gossip", 25, 100) == 5


def test_hlo_instructions_and_their_op_names():
    text = """
%fused_computation.3 (param_0.7: f32[4], param_1.1: s32[4]) -> f32[4] {
  %param_0.7 = f32[4]{0} parameter(0)
  %reshape.1 = f32[4]{0} reshape(%param_0.7), metadata={op_name="jit(s)/step.fwd_bwd/vmap(transpose(jvp()))/while"}
  %neg.2 = f32[4]{0} negate(%reshape.1), metadata={op_name="jit(s)/step.fwd_bwd/vmap(transpose(jvp()))/neg"}
  ROOT %scatter.10 = f32[4]{0} scatter(%neg.2, %param_1.1)
}

%wide.body.7 (wide.param: (u32[], f32[4])) -> (u32[], f32[4]) {
  %wide.param = (u32[]{:T(128)}, f32[4]{0:T(1024)}) parameter(0)
  %get-tuple-element.1 = f32[4]{0:T(1024)} get-tuple-element(%wide.param), index=1
  %reshape.9 = f32[4]{0:T(1024)} reshape(%get-tuple-element.1)
  ROOT %tuple.2 = (u32[], f32[4]{0}) tuple(%get-tuple-element.1, %reshape.9)
}

ENTRY %main (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %fusion.12 = f32[4]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_type="mul" op_name="jit(s)/step.gossip/mul" source_file="x.py" source_line=3}
  %dynamic-update-slice.3 = f32[4]{0} dynamic-update-slice(%p), metadata={op_name="jit(s)/step.fwd_bwd/vmap(jvp(f))/dus"}
  %fusion.3 = f32[4]{0:T(8,128)} fusion(%p, %i), kind=kCustom, calls=%fused_computation.3
  %tuple.5 = (u32[], f32[4]{0}) tuple(%p, %fusion.3)
  %while.1 = (u32[], f32[4]{0}) while(%tuple.5), condition=%cond.7, body=%wide.body.7
  ROOT %copy.1 = f32[4]{0} copy(%fusion.12)
}"""
    fwd = "jit(s)/step.fwd_bwd/vmap(transpose(jvp()))/neg"
    got = pt.hlo_scopes(text)
    assert got["fusion.12"] == "jit(s)/step.gossip/mul"
    assert got["dynamic-update-slice.3"] == \
        "jit(s)/step.fwd_bwd/vmap(jvp(f))/dus"
    # no metadata of their own: the fusion's root op_name, the operand's,
    # the loop's
    assert got["fusion.3"] == got["tuple.5"] == got["while.1"] == fwd
    assert got["reshape.9"] == got["get-tuple-element.1"] == fwd
    assert got["copy.1"] == "jit(s)/step.gossip/mul"
    assert "p" not in got


OPS = [("fusion.1", 0, 10), ("gossip_reduce_w_resident_pallas.1", 10, 5),
       ("fusion.2", 12, 4), ("gossip_apply_w_resident_pallas.1", 30, 20),
       ("gossip_apply_w_resident_pallas.1", 60, 10)]
HOST = [("bench.window", 0, 100), ("bench.next_wbatch", 16, 12),
        ("bench.step_fn", 52, 6)]


def test_idle_gaps_take_the_innermost_program_span():
    # gaps (16, 30), (50, 60), (70, 80) inside one step
    spans = [("train.step", 0, 80), ("train.batch.generate", 17, 9),
             ("train.batch.place", 26, 3), ("train.dispatch", 54, 1)]
    assert pt.idle_gaps(OPS, spans, HOST, 0, 80) == [
        ["train.batch.generate", 14e-9], ["train.dispatch", 10e-9],
        ["train.step", 10e-9]]


def test_idle_gaps_fall_back_to_the_benchmark_spans_then_host():
    spans = [("train.step", 0, 40), ("train.batch.generate", 17, 9)]
    assert pt.idle_gaps(OPS, spans, HOST, 0, 100) == [
        ["host", 30e-9], ["train.batch.generate", 14e-9],
        ["bench.step_fn", 10e-9]]
    assert pt.idle_gaps(OPS, [], HOST, 0, 100) == tr.idle_gaps(OPS, HOST,
                                                               0, 100)


@pytest.mark.parametrize("name", SCOPE_READERS)
def test_the_scope_readers_read_nothing_without_a_trace(name):
    read = harness.metric_reader(name)
    assert read({}) is None
    assert read({"trace": tr.Trace(devices=[], host=[]),
                 "trace_window": (0, 10)}) is None


@pytest.mark.parametrize("name", SCOPE_READERS)
def test_the_scope_readers_read_nothing_from_a_program_without_scopes(name):
    ctx = {"trace": tr.Trace(devices=[[("fusion.1", 0, 10)]], host=[]),
           "trace_window": (0, 10), "steps": 1,
           "step_scopes": {"fusion.1": "jit(step)/mul"}}
    assert harness.metric_reader(name)(ctx) is None


def test_the_scope_readers_on_the_reduced_cells_compiled_step():
    ctx = {"cell": cells.reduced_cell("smollm-w4-int8"), "seed": 7,
           "chips": 1, "trace_window": (0, 1000), "steps": 2}
    scopes = _scopes.step_scopes(ctx)
    fwd = [k for k, v in scopes.items() if pt.in_scope(v, "step.fwd_bwd")]
    gossip = [k for k, v in scopes.items() if pt.in_scope(v, "step.gossip")]
    assert any("transpose(" in scopes[k] for k in fwd)
    assert fwd and gossip
    ctx["trace"] = tr.Trace(devices=[[(fwd[0], 0, 300), (gossip[0], 300, 50),
                                      ("no-such-op.1", 350, 7)]], host=[])
    assert harness.metric_reader("fwd_bwd_device_ms")(ctx) == 150e-6
    assert harness.metric_reader("gossip_device_ms")(ctx) == 25e-6
    # a step whose instructions do not name most of the window's time is
    # not the program that ran
    ctx["trace"].devices[0].append(("no-such-op.2", 400, 100))
    assert harness.metric_reader("fwd_bwd_device_ms")(ctx) is None
