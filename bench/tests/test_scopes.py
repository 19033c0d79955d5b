"""Attribution of device time to the program's layers (bench/scopes.py):
scope paths read from HLO text, own time and idle time by scope on
hand-made events with known answers, and on a period recorded on the chip
(the program's compiled step is checked in tests/test_step_scopes.py)."""
import gzip
import json
from pathlib import Path

import pytest

from bench import scopes, trace

HLO = """HloModule step

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.1 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(step)/jvp(model.layers)/while/body/model.block/mul"}
}

%body.1 (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %get-tuple-element.1 = f32[8]{0} get-tuple-element(%p), index=1
  %copy.3 = f32[8]{0} copy(%get-tuple-element.1)
  %fusion.2 = f32[8]{0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/jvp(model.layers)/while/body/closed_call/model.block/model.mlp/dot_general" source_file="x.py" source_line=3}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%get-tuple-element.1, %fusion.2)
}

ENTRY %main.9 (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %while.4 = (s32[], f32[8]{0}) while(%t), condition=%cond.1, body=%body.1, metadata={op_name="jit(step)/transpose(jvp(model.layers))/while"}
  %custom-call.5 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/cond/branch_1_fun/sophia.estimate/loss.lm/pallas_call"}
  %fusion.7 = f32[8]{0:T(1024)} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %dynamic-update-slice.8 = f32[8]{0:T(1024)} dynamic-update-slice(%custom-call.5, %a, %c), backend_config={"x":"y"}
  %copy.9 = f32[8]{0} copy(%a)
  ROOT %add.6 = f32[8]{0} add(%a, %a), metadata={op_name="jit(step)/add"}
}
"""


def test_op_scopes_from_hlo_text():
    got = scopes.op_scopes(HLO)
    assert got["fusion.2"] == "model.layers/model.block/model.mlp"
    assert got["multiply.1"] == "model.layers/model.block"
    # transforms wrap a scope's name, and do not hide it
    assert got["while.4"] == "model.layers"
    assert got["custom-call.5"] == "sophia.estimate/loss.lm"
    # no program scope, and no metadata outside any call: no scope
    assert got["add.6"] == "" and got["a"] == ""
    # an instruction without metadata takes its fused body's path, its
    # operands' or its caller's
    assert got["fusion.7"] == "model.layers/model.block"
    assert got["dynamic-update-slice.8"] == "sophia.estimate/loss.lm"
    assert got["copy.3"] == "model.layers"
    assert got["get-tuple-element.1"] == "model.layers"
    assert got["copy.9"] == ""


def test_scope_path_keeps_order_and_drops_repeats():
    assert scopes.scope_path(
        "jit(f)/cond/branch_1_fun/sophia.estimate/transpose(jvp("
        "model.layers))/while/body/model.block/model.attn/model.block") == \
        "sophia.estimate/model.layers/model.block/model.attn"
    assert scopes.scope_path("jit(f)/model.layersX/dot") == ""


def _trace():
    # one chip, window [0, 10): a while over the layers holds three ops;
    # idle gaps [0, 1), [5, 6), [7.5, 8) and [8.5, 10)
    ops = [[("while", 1.0, 5.0), ("fusion", 1.0, 2.0),
            ("bitcast_dynamic-update-slice_fusion", 2.0, 2.5),
            ("fusion", 2.5, 4.0), ("_sophia_kernel", 6.0, 7.0),
            ("fusion", 7.0, 7.5), ("copy", 8.0, 8.5)]]
    paths = [["model.layers", "model.layers/model.block/model.attn",
              "model.layers", "sophia.estimate/model.layers/model.block",
              "optimizer.update", "train.grad_clip", ""]]
    spans = [("bench.window", 0.0, 10.0),
             ("data.batch_at", 0.0, 0.4), ("bench.dispatch", 0.4, 0.8),
             ("bench.wait", 0.8, 5.0), ("data.batch_at", 5.0, 5.5),
             ("bench.dispatch", 5.5, 5.8), ("bench.wait", 5.8, 9.0)]
    return scopes.ScopedTrace(ops, spans, (0.0, 10.0), paths)


def test_own_time_by_scope():
    tr = _trace()
    got = dict(tr.top_scopes(10))
    # the while's own time is the 1 s in which none of its ops runs
    assert got == pytest.approx({
        "model.layers": 1.5, "model.layers/model.block/model.attn": 1.0,
        "sophia.estimate/model.layers/model.block": 1.5,
        "optimizer.update": 1.0, "train.grad_clip": 0.5, "(none)": 0.5})
    assert sum(got.values()) == pytest.approx(tr.busy_s())
    assert dict(tr.top_ops_in(lambda p: p == "model.layers", 5)) == \
        pytest.approx({"while": 1.0,
                       "bitcast_dynamic-update-slice_fusion": 0.5})


def test_idle_in_step_named_by_the_op_that_ends_the_gap():
    tr = _trace()
    got = dict(tr.idle_in_step(5))
    assert tr.breakdown() == {"scopes": tr.top_scopes(10),
                              "idle_in_step": tr.idle_in_step(5)}
    # [0.4, 1) ends at the attention fusion (innermost of the ops starting
    # at 1); [5.5, 6) at the update; [7.5, 8) at an unscoped copy;
    # [8.5, 9) has no op after it
    assert got == pytest.approx({
        "model.layers/model.block/model.attn": 0.6,
        "optimizer.update": 0.5, "(none)": 1.0})


def test_idle_inside_the_data_spans():
    tr = _trace()
    assert tr.steps() == 2
    assert tr.idle_in_span_s("data.batch_at") == pytest.approx(0.9)
    assert scopes.data_idle_ms_per_step(tr, 2) == pytest.approx(450.0)


def test_layer_metrics():
    tr = _trace()
    assert tr.busy_s() == pytest.approx(6.0)
    assert scopes.estimate_device_pct(tr) == pytest.approx(
        100.0 * 1.5 / 4.5)
    assert scopes.optimizer_ms_per_step(tr, 2) == pytest.approx(750.0)
    assert scopes.layer_scan_device_pct(tr) == pytest.approx(25.0)


def test_record_round_trip_and_old_records():
    tr = _trace()
    rec = scopes.to_record(tr)
    back = scopes.from_record(json.loads(json.dumps(rec)))
    assert dict(back.top_scopes(10)) == pytest.approx(
        dict(tr.top_scopes(10)))
    assert back.idle_in_span_s("data.batch_at") == pytest.approx(0.9)
    # the benchmark's reader takes the record as it took the old ones
    old = trace.from_record(rec)
    assert old.busy_s() == pytest.approx(tr.busy_s())
    # a record without scopes: every op under none
    rec.pop("scopes")
    assert scopes.from_record(rec).top_scopes(3) == \
        [["(none)", pytest.approx(tr.busy_s())]]


def test_recorded_period_without_scopes():
    """The period recorded before the program had scopes: all its device
    time is own time of no scope."""
    path = Path(__file__).with_name("data") / \
        "gpt2s.sophia_g.s1024.period.json.gz"
    with gzip.open(path, "rt") as f:
        tr = scopes.from_record(json.load(f))
    (name, s), = tr.top_scopes(10)
    assert name == "(none)"
    assert s == pytest.approx(tr.busy_s(), rel=1e-3)


#: one refresh period (ten steps) of ``gpt2s.sophia_g.s1024`` on one TPU v5e
#: with the program's scopes and spans, cut from a ``--keep-trace`` window
#: (30 s, seed 3141592653) and reduced by ``from_xplane`` with the
#: ``op_scopes`` of the same compiled step
SCOPED = Path(__file__).with_name("data") / \
    "gpt2s.sophia_g.s1024.scoped_period.json.gz"
#: what that whole window read (PERF.md §6): a period reads within 15%
WINDOW = {"estimate_pct": 4.511, "optimizer_ms": 16.233,
          "layer_scan_pct": 11.923, "data_idle_ms": 5.592}


@pytest.fixture(scope="module")
def scoped():
    with gzip.open(SCOPED, "rt") as f:
        return scopes.from_record(json.load(f))


def test_recorded_period_reads_as_its_window(scoped):
    steps = scoped.steps()
    assert steps == 10
    got = {"estimate_pct": scopes.estimate_device_pct(scoped),
           "optimizer_ms": scopes.optimizer_ms_per_step(scoped, steps),
           "layer_scan_pct": scopes.layer_scan_device_pct(scoped),
           "data_idle_ms": scopes.data_idle_ms_per_step(scoped, steps)}
    assert got == pytest.approx(WINDOW, rel=0.15)


def test_recorded_period_attributes_its_device_time(scoped):
    """Every scope of the step holds device time, little is left under
    none, and each kernel sits in its layer."""
    by = dict(scoped.top_scopes(100))
    assert sum(by.values()) == pytest.approx(scoped.busy_s(), rel=1e-3)
    assert by.get("(none)", 0.0) < 0.15 * scoped.busy_s()
    found = {s for path in by for s in path.split("/")}
    assert set(scopes.SCOPES) <= found

    def kernel_paths(name):
        return {p for d, sc in zip(scoped.ops, scoped.scopes)
                for (n, _, _), p in zip(d, sc) if n == name}

    assert kernel_paths("_sophia_kernel") == {"optimizer.update"}
    assert kernel_paths("_ce_fwd_sample_kernel") == {"sophia.estimate/loss.lm"}
    assert kernel_paths("_fwd_kernel") == {
        "model.layers/model.block/model.attn",
        "sophia.estimate/model.layers/model.block/model.attn"}
