"""The program's named passes: scopes read from HLO metadata, their device
time from a synthetic trace, their readers, and the scopes on the cell's
compiled step at smoke widths."""

import re

import jax
import pytest

from bench import scopes, spec
from bench import trace_reduce as tr
from bench.trace_reduce import Op, Span

# A compiled module's text as the TPU prints it, with op_name metadata: a
# fusion whose root is unscoped but whose body is mostly dps.acts, a tie,
# a fusion that fuses a dot, nested scopes, and bare instructions.
HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8], param_1.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %param_1.1 = f32[8]{0} parameter(1)
  %constant.1 = f32[] constant(0.5)
  %broadcast.1 = f32[8]{0} broadcast(%constant.1), dimensions={}, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/dps.acts/mul"}
  %multiply.1 = f32[8]{0} multiply(%param_0.1, %broadcast.1), metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/dps.acts/mul"}
  %floor.1 = f32[8]{0} floor(%multiply.1), metadata={op_name="jit(train_step)/checkpoint/rematted_computation/dps.acts/floor"}
  %multiply.2 = f32[8]{0} multiply(%param_1.1, %param_1.1), metadata={op_name="jit(train_step)/transpose(jvp())/checkpoint/dps.grads/mul"}
  ROOT %add.1 = f32[8]{0} add(%floor.1, %multiply.2), metadata={op_name="jit(train_step)/transpose(jvp())/add_any"}
}

%fused_computation.2 (param_0.2: f32[8], param_1.2: f32[], param_2.2: f32[], param_3.2: f32[], param_4.2: f32[], /*index=5*/param_5.2: f32[]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %sqrt.1 = f32[8]{0} sqrt(%param_0.2), metadata={op_name="jit(train_step)/optim/sqrt"}
  %negate.1 = f32[8]{0} negate(%sqrt.1), metadata={op_name="jit(train_step)/dps.weights/neg"}
  ROOT %add.2 = f32[8]{0} add(%negate.1, %sqrt.1), metadata={op_name="jit(train_step)/optim/add"}
}

%fused_computation.5 (param_0.5: f32[8]) -> (f32[8]) {
  %param_0.5 = f32[8]{0} parameter(0)
  %sqrt.5 = f32[8]{0} sqrt(%param_0.5), metadata={op_name="jit(train_step)/optim/sqrt"}
  %negate.5 = f32[8]{0} negate(%sqrt.5), metadata={op_name="jit(train_step)/dps.weights/neg"}
  ROOT %tuple.5 = (f32[8]{0}) tuple(%negate.5)
}

%fused_computation.3 (param_0.3: bf16[8,8], param_1.3: bf16[8,8]) -> f32[8,8] {
  %param_0.3 = bf16[8,8]{1,0} parameter(0)
  %param_1.3 = bf16[8,8]{1,0} parameter(1)
  %convert.3 = bf16[8,8]{1,0} convert(%param_0.3), metadata={op_name="jit(train_step)/dps.weights/convert_element_type"}
  %multiply.3 = bf16[8,8]{1,0} multiply(%convert.3, %convert.3), metadata={op_name="jit(train_step)/dps.weights/mul"}
  ROOT %convolution.3 = f32[8,8]{1,0} convolution(%multiply.3, %param_1.3), dim_labels=bf_io->bf, metadata={op_name="jit(train_step)/jvp()/dot_general"}
}

%fused_computation.4 (param_0.4: f32[8]) -> f32[8] {
  %param_0.4 = f32[8]{0} parameter(0)
  %fusion.40 = (f32[8]{0}) fusion(%param_0.4), kind=kLoop, calls=%fused_computation.5
  %abs.4 = f32[8]{0} abs(%fusion.40), metadata={op_name="jit(train_step)/dps.weights/abs"}
  ROOT %exp.4 = f32[8]{0} exponential(%abs.4), metadata={op_name="jit(train_step)/exp"}
}

%add_computation (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%x, %y), metadata={op_name="jit(train_step)/dps.acts/reduce_sum"}
}

ENTRY %main.1 (a.1: f32[8], b.1: f32[8], w.1: bf16[8,8]) -> f32[8] {
  %a.1 = f32[8]{0} parameter(0)
  %b.1 = f32[8]{0} parameter(1)
  %w.1 = bf16[8,8]{1,0} parameter(2)
  %fusion.1 = f32[8]{0} fusion(%a.1, %b.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/transpose(jvp())/add_any"}
  %fusion.2 = f32[8]{0} fusion(%a.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(train_step)/optim/add"}
  %fusion.5 = (f32[8]{0}) fusion(%a.1), kind=kLoop, calls=%fused_computation.5, metadata={op_name="jit(train_step)/optim/sqrt"}
  %fusion.3 = f32[8,8]{1,0} fusion(%w.1, %w.1), kind=kOutput, calls=%fused_computation.3, metadata={op_name="jit(train_step)/jvp()/dot_general"}
  %fusion.4 = f32[8]{0} fusion(%a.1), kind=kLoop, calls=%fused_computation.4, metadata={op_name="jit(train_step)/exp"}
  %reduce.5 = f32[] reduce(%a.1, %constant.1), dimensions={0}, to_apply=%add_computation, metadata={op_name="jit(train_step)/optim/dps.grads/reduce_sum"}
  %copy.6 = f32[8]{0} copy(%b.1), metadata={op_name="jit(train_step)/dps.grads/optim_x/copy"}
  ROOT %add.7 = f32[8]{0} add(%a.1, %b.1)
}
"""


def test_hlo_scopes_rules():
    s = scopes.hlo_scopes(HLO)
    # a fusion takes its body's majority, not its unscoped root's op_name
    # (dps.acts 3 votes, dps.grads 1, none 1; the parameters and the
    # constant do not vote)
    assert s["fusion.1"] == "dps.acts"
    # optim 2, dps.weights 1 (the header's ``/*index=5*/`` does not hide
    # the computation)
    assert s["fusion.2"] == "optim"
    # one optim and one dps.weights vote, the tuple does not vote: a tie
    assert s["fusion.5"] == "none"
    # a dot fusion votes too (the reduction then counts it as matmul)
    assert s["fusion.3"] == "dps.weights"
    assert s["convolution.3"] == "none"
    # a nested fusion casts one vote, its own majority (a tie, "none"):
    # none 2 (the nested fusion and exp), dps.weights 1
    assert s["fusion.4"] == "none"
    # the innermost segment that is a name wins; a segment that only
    # contains a name does not match; a reduce keeps its own op_name
    assert s["reduce.5"] == "dps.grads"
    assert s["copy.6"] == "dps.grads"
    # unscoped and metadata-free instructions are "none"
    assert s["add.7"] == "none"
    assert s["a.1"] == "none"
    # instructions inside fused computations get their own scope
    assert s["multiply.2"] == "dps.grads"
    assert s["floor.1"] == "dps.acts"


def test_hlo_scopes_takes_only_the_names_it_is_given():
    s = scopes.hlo_scopes(HLO, names=("optim",))
    assert s["reduce.5"] == "optim"
    assert s["fusion.1"] == "none"


def _ops(*triples):
    return [Op(n, float(s), float(d)) for n, s, d in triples]


def test_scope_seconds_split_class_other_and_skip_matmuls():
    classes, sc = tr.hlo_classes(HLO), scopes.hlo_scopes(HLO)
    spans = [Span("bench.window", 0, 100)]
    dev0 = _ops(("%fusion.1 = x", 0, 10),          # other, dps.acts
                ("%fusion.3 = x", 10, 30),         # matmul: in no scope
                ("%reduce.5 = x", 40, 5),          # other, dps.grads
                ("%copy.6 = x", 95, 10),           # clipped to 5
                ("%add.7 = x", 50, 4),             # other, none
                ("%unknown.9 = x", 60, 2),         # not in the HLO: none
                ("%fusion.2 = x", 120, 5))         # outside the window
    dev1 = _ops(("%fusion.1 = x", 0, 20), ("%fusion.4 = x", 30, 6))
    got = scopes.scope_seconds({0: dev0, 1: dev1}, spans, classes, sc, "tpu")
    assert set(got) == {*scopes.SCOPES, "none"}
    assert got["dps.acts"] == pytest.approx((10 + 20) / 2 * 1e-9)
    assert got["dps.grads"] == pytest.approx((5 + 5) / 2 * 1e-9)
    assert got["none"] == pytest.approx((4 + 2 + 6) / 2 * 1e-9)
    assert got["dps.weights"] == 0.0 and got["optim"] == 0.0
    red = tr.reduce({0: dev0, 1: dev1}, spans, classes, "tpu")
    assert sum(got.values()) == pytest.approx(red.class_s["other"])
    assert red.class_s["matmul"] == pytest.approx(30 / 2 * 1e-9)


def test_scope_seconds_refuses_a_cpu_trace():
    spans = [Span("bench.window", 0, 10)]
    with pytest.raises(ValueError, match="TPU"):
        scopes.scope_seconds({0: []}, spans, {}, {}, "cpu")


def _trace_metrics(ctx, hlo, trace_dir, platform, reader):
    """Stands in for ``bench/run.py``'s frame that calls the readers."""
    return reader(ctx)


@pytest.mark.parametrize("metric,want", [
    ("dps.acts_ms_per_step", 1e3 * 15e-9 / 5),
    ("dps.grads_ms_per_step", 1e3 * 7e-9 / 5),
    ("dps.weights_ms_per_step", None),
    ("optim.ms_per_step", None),
])
def test_readers_find_the_harness_frame(monkeypatch, metric, want):
    spans = [Span("bench.window", 0, 100)]
    ops = {0: _ops(("%fusion.1 = x", 0, 15), ("%reduce.5 = x", 20, 7),
                   ("%fusion.3 = x", 30, 40))}
    reads = []

    def read_xplane(trace_dir):
        reads.append(trace_dir)
        return ops, spans

    monkeypatch.setattr(tr, "read_xplane", read_xplane)
    ctx = {"steps": 5}
    got = _trace_metrics(ctx, HLO, "/trace", "tpu",
                         spec.metric_reader(metric))
    assert got == (None if want is None else pytest.approx(want))
    # the other readers of the run take the memo: one read of the trace
    for other in ("dps.acts_ms_per_step", "optim.ms_per_step"):
        _trace_metrics(ctx, HLO, "/trace", "tpu", spec.metric_reader(other))
    assert reads == ["/trace"]


def test_readers_without_the_harness_frame_read_nothing():
    assert spec.metric_reader("dps.acts_ms_per_step")({"steps": 1}) is None


_DOT = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\S+\s+"
                  r"(dot|convolution)\(", re.M)


def test_smoke_step_carries_each_scope():
    """The cell's compiled step at smoke widths: every scope names some
    computed instruction, and no matmul sits in a DPS or optimizer scope."""
    from bench import inputs, program

    cell = spec.smoke(spec.load_cell("internvl2-26b-l1.train-dps"))
    prog = program.build(cell, jax.devices()[:1], smoke=True)
    key = inputs.root_key(7)
    state = jax.eval_shape(prog.make_state, key)
    batch = jax.eval_shape(prog.make_batch, key, 0)
    hlo = prog.step.trace(state, batch).lower().compile().as_text()
    s = scopes.hlo_scopes(hlo)
    params = {m.group(1) for m in re.finditer(
        r"^\s*%?([\w.\-]+)\s*=\s*\S+\s+parameter\(", hlo, re.M)}
    found = {v for k, v in s.items() if k not in params}
    assert set(scopes.SCOPES) <= found
    dots = [m.group(1) for m in _DOT.finditer(hlo)]
    assert dots
    assert all(s[d] == "none" for d in dots), \
        {d: s[d] for d in dots if s[d] != "none"}
