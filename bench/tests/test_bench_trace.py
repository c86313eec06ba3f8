"""Trace reduction on a synthetic event list and a recorded HLO text."""

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Op, Span

# The shape of a TPU v5e compiled module's text: fused computations, an
# entry computation, a while loop body, a Pallas kernel and collectives.
HLO = """HloModule jit_step, is_scheduled=true

%fused_computation.3 (param_0.12: bf16[8,8], param_1.13: bf16[8,8]) -> f32[8,8] {
  %param_0.12 = bf16[8,8]{1,0:T(8,128)(2,1)S(1)} parameter(0)
  %param_1.13 = bf16[8,8]{1,0:T(8,128)(2,1)} parameter(1)
  ROOT %convolution.7 = f32[8,8]{1,0:T(8,128)} convolution(%param_0.12, %param_1.13), dim_labels=bf_io->bf
}

%fused_computation.4 (param_0.1: f32[8,8]) -> f32[8,8] {
  %param_0.1 = f32[8,8]{1,0} parameter(0)
  ROOT %tanh.1 = f32[8,8]{1,0} tanh(%param_0.1)
}

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %gte = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %fusion.9 = f32[8,8]{1,0} fusion(%gte), kind=kLoop, calls=%fused_computation.4
  %dot.2 = f32[8,8]{1,0} dot(%gte, %gte), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %tuple.1 = (s32[], f32[8,8]{1,0}) tuple(%gte, %dot.2)
}

ENTRY %main.1 (a.1: bf16[8,8], b.1: bf16[8,8]) -> f32[8,8] {
  %a.1 = bf16[8,8]{1,0} parameter(0)
  %b.1 = bf16[8,8]{1,0} parameter(1)
  %fusion.2 = f32[8,8]{1,0:T(8,128)} fusion(%a.1, %b.1), kind=kOutput, calls=%fused_computation.3
  %while.1 = (s32[], f32[8,8]{1,0}) while(%t), condition=%cond, body=%body
  %all-to-all.3 = (s8[2,4]{1,0}, s8[2,4]{1,0}) all-to-all(%x, %y), replica_groups={{0,1}}
  %all-gather-start.1 = (s8[4]{0}, s8[8]{0}) all-gather-start(%z), dimensions={0}
  %custom-call.5 = f32[8]{0} custom-call(%w), custom_call_target="tpu_custom_call", backend_config={"custom_call_config": {"body": "x"}}
  ROOT %add.3 = f32[8,8]{1,0} add(%fusion.2, %fusion.2)
}
"""


def test_hlo_classes_cover_each_class():
    c = tr.hlo_classes(HLO)
    assert c["fusion.2"] == "matmul"          # fuses a convolution
    assert c["dot.2"] == "matmul"             # bare dot in a loop body
    assert c["fusion.9"] == "other"           # elementwise fusion
    assert c["all-to-all.3"] == "collective"
    assert c["all-gather-start.1"] == "collective"
    assert c["custom-call.5"] == "kernel"
    assert c["add.3"] == "other"
    assert c["while.1"] == "container"


def test_trace_op_names_are_looked_up_by_instruction():
    c = tr.hlo_classes(HLO)
    name = ("%fusion.2 = f32[8,8]{1,0:T(8,128)} fusion(bf16[8,8] %a.1, "
            "bf16[8,8] %b.1), kind=kOutput, calls=%fused_computation.3")
    assert tr.classify(name, c) == "matmul"
    assert tr.classify("%unknown.1 = f32[] add()", c) == "other"


def _ops(*triples):
    return [Op(n, float(s), float(d)) for n, s, d in triples]


def test_reduce_busy_classes_exposed_and_gaps():
    c = tr.hlo_classes(HLO)
    spans = [Span("bench.window", 0, 100), Span("bench.batch", 0, 10),
             Span("bench.dispatch", 10, 12), Span("bench.fetch", 60, 80)]
    dev0 = _ops(("%fusion.2 = x", 10, 20),        # matmul 10..30
                ("%add.3 = x", 25, 15),           # overlaps: 25..40
                ("%all-to-all.3 = x", 35, 15),    # 35..50, 10 exposed
                ("%custom-call.5 = x", 90, 20),   # 90..110, clipped to 100
                ("%while.1 = x", 10, 40))         # spans its body: no class
    dev1 = _ops(("%fusion.2 = x", 10, 40))        # 10..50
    red = tr.reduce({0: dev0, 1: dev1}, spans, c, "tpu")
    assert red.window_s == pytest.approx(100e-9)
    # device 0 busy 10..50 and 90..100 = 50; device 1 busy 40
    assert red.busy_s == pytest.approx(45e-9)
    assert red.class_s["matmul"] == pytest.approx((20 + 40) / 2 * 1e-9)
    assert red.class_s["other"] == pytest.approx(15 / 2 * 1e-9)
    assert red.class_s["collective"] == pytest.approx(15 / 2 * 1e-9)
    assert red.class_s["kernel"] == pytest.approx(10 / 2 * 1e-9)
    assert red.exposed_collective_s == pytest.approx(10 / 2 * 1e-9)
    # device 0's gaps: 0..10 (batch), 50..90 (fetch covers 60..80)
    assert red.idle_gaps[0] == ["bench.fetch", pytest.approx(40e-9)]
    assert red.idle_gaps[1] == ["bench.batch", pytest.approx(10e-9)]
    assert red.device_ops[0][0].endswith("[matmul]")


def test_reduce_refuses_a_cpu_trace():
    spans = [Span("bench.window", 0, 10)]
    with pytest.raises(ValueError, match="TPU"):
        tr.reduce({0: _ops(("%add.3 = x", 0, 5))}, spans, {}, "cpu")
