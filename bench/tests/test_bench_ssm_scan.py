"""The Mamba2 cell: its SSD scan's roofline counts against numbers worked by
hand, the scan's device time from a synthetic trace, the two readers, the
``ssm.scan`` scope on the cell's compiled step, and ``correct`` at smoke
widths (the sound program passes, the control and each fault fail)."""

import dataclasses
import json
import re

import jax
import pytest

from bench import correct, inputs, program, run, scopes, spec, ssm_scan
from bench import trace_reduce as tr
from bench.trace_reduce import Op, Span

CELL = "mamba2-1.3b-l16.train-dps"
SEED = 424242
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


def _model():
    return spec.load_cell(CELL).config["model"]


def test_roofline_counts_of_the_cell():
    """4 x 2048 positions, 16 layers, Q 256, H 64, P 64, N 128, bf16.

    Operations per position and layer: Q N + Q H P + 4 N H P = 32,768 +
    1,048,576 + 2,097,152 = 3,178,496; times 8,192 positions, 16 layers and
    4 passes.  Bytes per layer and pass: x·dt and y, 8,192 x 4,096 x 2 B
    each; B and C, 8,192 x 128 x 2 B each; log-decays 8,192 x 64 x 4 B;
    chunk states 32 x 64 x 64 x 128 x 4 B: 207,618,048, times 64.  The
    bytes take 16.2 ms at 819 GB/s, the operations 8.5 ms at 197 TFLOP/s."""
    c = _model()
    assert ssm_scan.flops_per_step(c, 4, 2048) == 3_178_496 * 8192 * 16 * 4
    per_pass = (2 * 8192 * 4096 * 2 + 2 * 8192 * 128 * 2 + 8192 * 64 * 4
                + 32 * 64 * 64 * 128 * 4)
    assert per_pass == 207_618_048
    assert ssm_scan.bytes_per_step(c, 4, 2048) == per_pass * 64
    assert ssm_scan.roofline_s(c, 4, 2048, V5E) == pytest.approx(
        per_pass * 64 / 819e9)
    assert ssm_scan.roofline_s(c, 4, 2048, V5E) == pytest.approx(16.22e-3,
                                                                 rel=1e-3)


def test_roofline_counts_a_short_chunk_and_a_ragged_tail():
    """One row of 300 positions, chunk 256, two chunks (the second padded),
    one layer at tiny widths in float32: H 2, P 4, N 8.  Operations per
    position: 256 x 8 + 256 x 2 x 4 + 4 x 8 x 2 x 4 = 4,352; a sequence
    shorter than the chunk takes the sequence as its chunk."""
    c = {"d_model": 4, "ssm_expand": 2, "ssm_head_dim": 4, "ssm_state": 8,
         "ssm_chunk": 256, "n_layers": 1, "dtype": "float32"}
    assert ssm_scan.flops_per_step(c, 1, 300) == 4 * 4_352 * 300
    per_pass = 300 * (2 * 8 + 2 * 8) * 4 + 300 * 2 * 4 + 2 * 2 * 4 * 8 * 4
    assert ssm_scan.bytes_per_step(c, 1, 300) == 4 * per_pass
    # 100 positions: one chunk of 100
    assert ssm_scan.flops_per_step(c, 1, 100) == 4 * 100 * (
        100 * 8 + 100 * 2 * 4 + 4 * 8 * 2 * 4)


# A compiled module's text with op_name metadata: a matmul fusion and an
# elementwise fusion of the scan, a projection dot outside it, a while
# loop (a container) inside it, and a fusion that mixes both.
HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8,8], param_1.1: f32[8,8]) -> f32[8,8] {
  %param_0.1 = f32[8,8]{1,0} parameter(0)
  %param_1.1 = f32[8,8]{1,0} parameter(1)
  %exponential.1 = f32[8,8]{1,0} exponential(%param_0.1), metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/ssm.scan/exp"}
  ROOT %dot.1 = f32[8,8]{1,0} dot(%exponential.1, %param_1.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/ssm.scan/bcqkh,bckhp->bcqhp/dot_general"}
}

%fused_computation.2 (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  %negate.2 = f32[8]{0} negate(%param_0.2), metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/ssm.scan/neg"}
  ROOT %exponential.2 = f32[8]{0} exponential(%negate.2), metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/ssm.scan/exp"}
}

%fused_computation.3 (param_0.3: f32[8]) -> f32[8] {
  %param_0.3 = f32[8]{0} parameter(0)
  %negate.3 = f32[8]{0} negate(%param_0.3), metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/mul"}
  ROOT %exponential.3 = f32[8]{0} exponential(%negate.3), metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/ssm.scan/exp"}
}

%body.4 (p.4: f32[8]) -> f32[8] {
  %p.4 = f32[8]{0} parameter(0)
  ROOT %multiply.4 = f32[8]{0} multiply(%p.4, %p.4), metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/ssm.scan/while/body/mul"}
}

%cond.4 (p.5: f32[8]) -> pred[] {
  %p.5 = f32[8]{0} parameter(0)
  ROOT %constant.5 = pred[] constant(false)
}

ENTRY %main.1 (a.1: f32[8,8], b.1: f32[8,8], v.1: f32[8]) -> f32[8,8] {
  %a.1 = f32[8,8]{1,0} parameter(0)
  %b.1 = f32[8,8]{1,0} parameter(1)
  %v.1 = f32[8]{0} parameter(2)
  %fusion.1 = f32[8,8]{1,0} fusion(%a.1, %b.1), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/ssm.scan/bcqkh,bckhp->bcqhp/dot_general"}
  %fusion.2 = f32[8]{0} fusion(%v.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/ssm.scan/exp"}
  %fusion.3 = f32[8]{0} fusion(%v.1), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/ssm.scan/exp"}
  %while.4 = f32[8]{0} while(%v.1), condition=%cond.4, body=%body.4, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/ssm.scan/while"}
  ROOT %dot.6 = f32[8,8]{1,0} dot(%a.1, %b.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/bsd,dk->bsk/dot_general"}
}
"""


def _ops(*triples):
    return [Op(n, float(s), float(d)) for n, s, d in triples]


def _scan_seconds(device_ops, platform="tpu"):
    return ssm_scan.scan_seconds(
        device_ops, [Span("bench.window", 0, 100)], tr.hlo_classes(HLO),
        scopes.hlo_scopes(HLO, (ssm_scan.SCOPE,)), platform)


def test_scan_seconds_count_every_class_but_containers():
    dev0 = _ops(("%fusion.1 = x", 0, 10),       # matmul, ssm.scan
                ("%fusion.2 = x", 10, 5),       # other, ssm.scan
                ("%fusion.3 = x", 20, 3),       # a tie: none
                ("%while.4 = x", 30, 40),       # container: left out
                ("%multiply.4 = x", 30, 4),     # the loop's body: counted
                ("%dot.6 = x", 70, 20),         # the projection: none
                ("%fusion.2 = x", 95, 10),      # clipped to 5
                ("%fusion.1 = x", 120, 5))      # outside the window
    dev1 = _ops(("%fusion.1 = x", 0, 6))
    assert _scan_seconds({0: dev0, 1: dev1}) == pytest.approx(
        ((10 + 5 + 4 + 5) + 6) / 2 * 1e-9)
    assert tr.hlo_classes(HLO)["fusion.1"] == "matmul"


def test_scan_seconds_refuse_a_cpu_trace():
    with pytest.raises(ValueError, match="TPU"):
        _scan_seconds({0: []}, platform="cpu")


def _trace_metrics(ctx, hlo, trace_dir, platform, reader):
    """Stands in for ``bench/run.py``'s frame that calls the readers."""
    return reader(ctx)


def test_readers_find_the_harness_frame(monkeypatch):
    ops = {0: _ops(("%fusion.1 = x", 0, 30), ("%dot.6 = x", 40, 20))}
    reads = []

    def read_xplane(trace_dir):
        reads.append(trace_dir)
        return ops, [Span("bench.window", 0, 100)]

    monkeypatch.setattr(tr, "read_xplane", read_xplane)
    ctx = {"steps": 3, "chips": 1, "cell": spec.load_cell(CELL),
           "peaks": V5E}
    ms = _trace_metrics(ctx, HLO, "/trace", "tpu",
                        spec.metric_reader("ssm.scan_ms_per_step"))
    assert ms == pytest.approx(1e3 * 30e-9 / 3)
    pct = _trace_metrics(ctx, HLO, "/trace", "tpu",
                         spec.metric_reader("ssm.scan_roofline"))
    best = ssm_scan.roofline_s(_model(), 4, 2048, V5E)
    assert pct == pytest.approx(100 * best / (30e-9 / 3))
    assert reads == ["/trace"]


@pytest.mark.parametrize("metric", ["ssm.scan_ms_per_step",
                                    "ssm.scan_roofline"])
def test_readers_read_nothing_without_the_scope_or_the_frame(monkeypatch,
                                                             metric):
    reader = spec.metric_reader(metric)
    assert reader({"steps": 1}) is None
    monkeypatch.setattr(tr, "read_xplane", lambda d: (
        {0: _ops(("%dot.6 = x", 0, 50))}, [Span("bench.window", 0, 100)]))
    unnamed = HLO.replace("ssm.scan/", "")
    ctx = {"steps": 1, "chips": 1, "cell": spec.load_cell(CELL),
           "peaks": V5E}
    assert _trace_metrics(ctx, unnamed, "/trace", "tpu", reader) is None


_OP_NAME = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*\S+\s+([\w\-]+)\('
                      r'.*op_name="([^"]*)"', re.M)
SCAN_EINSUMS = ("bcqn,bckn->bcqk", "bcqkh,bckhp->bcqhp",
                "bckn,bckh,bckhp->bchpn", "bcqn,bcqh,bchpn->bcqhp")
PROJECTIONS = ("bsd,dk->bsk", "bsk,kd->bsd")      # w_in, w_out


def test_smoke_step_names_the_scan_and_only_the_scan(monkeypatch):
    """The cell's compiled step at smoke widths with the cell's full layer
    remat: ``ssm.scan`` goes to the scan's forward, recomputed and backward
    ops, to each of its einsums' dots, and never to the input and output
    projections."""
    import repro.configs.base as base
    smoke_cfg = base.smoke
    monkeypatch.setattr(base, "smoke", lambda c: dataclasses.replace(
        smoke_cfg(c), remat="full"))
    prog = program.build(spec.smoke(spec.load_cell(CELL)),
                         jax.devices()[:1], smoke=True)
    assert prog.cfg.remat == "full"
    key = inputs.root_key(7)
    state = jax.eval_shape(prog.make_state, key)
    batch = jax.eval_shape(prog.make_batch, key, 0)
    hlo = prog.step.trace(state, batch).lower().compile().as_text()
    s = scopes.hlo_scopes(hlo, (ssm_scan.SCOPE,))
    passes, einsums, projections = set(), set(), []
    for m in _OP_NAME.finditer(hlo):
        name, opcode, op_name = m.groups()
        if s[name] == ssm_scan.SCOPE:
            passes.add("recomputed" if "rematted_computation" in op_name
                       else "backward" if "transpose(" in op_name
                       else "forward")
        if opcode == "dot":
            spec_ = op_name.split("/")[-2]
            if spec_ in SCAN_EINSUMS:
                assert s[name] == ssm_scan.SCOPE, op_name
                einsums.add(spec_)
        if any(f"/{p}/" in op_name for p in PROJECTIONS):
            projections.append(name)
            assert s[name] == scopes.NONE, op_name
    assert passes == {"forward", "recomputed", "backward"}
    assert einsums == set(SCAN_EINSUMS)
    assert projections


@pytest.fixture(scope="module")
def smoke_setup():
    cell = spec.smoke(spec.load_cell(CELL))
    prog = program.build(cell, jax.devices()[:1], smoke=True)
    cfg_d = program.model_dict(prog.cfg)
    key = inputs.root_key(SEED)
    ref = correct.reference_readings(cell, cfg_d, key)
    return cell, prog, cfg_d, key, ref


@pytest.mark.parametrize("fault,ok", [("none", True), ("control", False),
                                      ("half", False), ("unchanged", False)])
def test_correct_at_smoke_widths(smoke_setup, fault, ok):
    cell, prog, cfg_d, key, ref = smoke_setup
    if fault == "control":
        got = correct.reference_readings(cell, cfg_d, key, precision="fp8",
                                         stream=correct.CONTROL_STREAM)
    else:
        _, got = correct.program_readings(prog, key, fault)
    numbers = correct.compare(got, ref)
    assert correct.judge(numbers, cell.limits["smoke"]) is ok, numbers


def test_the_cell_runs_end_to_end_on_the_cpu(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 33 + 7),
                   "--seconds", "0.3", "--trace", "0"], rehearse=True)
    out = capsys.readouterr()
    assert rc == 0
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"] == {}          # no device metric off the chip
