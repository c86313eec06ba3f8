"""The SSD scan's Pallas kernels in the scan's metrics: a ``tpu_custom_call``
whose own ``op_name`` carries ``ssm.scan`` counts in the scan's device time
(``bench/ssm_scan.py``), and ``ssm.scan_kernel_pct`` reads its share of
that time; a program without the kernels, or without the scope, reads
nothing."""

import pytest

from bench import scopes, spec, ssm_scan
from bench import trace_reduce as tr
from bench.trace_reduce import Op, Span

CELL = "mamba2-1.3b-l16.train-dps"
V5E = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}

# A compiled step's text: the forward and backward kernels of the scan
# (custom calls, each named by its own op_name), a fusion of the scan's
# jnp remainder, and a projection dot outside the scan.
HLO = """HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %negate.1 = f32[8]{0} negate(%param_0.1), metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/ssm.scan/neg"}
  ROOT %exponential.1 = f32[8]{0} exponential(%negate.1), metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/ssm.scan/exp"}
}

ENTRY %main.1 (a.1: f32[8,8], b.1: f32[8,8], v.1: f32[8]) -> f32[8,8] {
  %a.1 = f32[8,8]{1,0} parameter(0)
  %b.1 = f32[8,8]{1,0} parameter(1)
  %v.1 = f32[8]{0} parameter(2)
  %ssd_intra_fwd.1 = f32[8,8]{1,0} custom-call(%a.1, %b.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/checkpoint/jvp(ssm.scan)/ssm.scan/ssd_intra_fwd/pallas_call"}
  %ssd_intra_bwd.1 = f32[8,8]{1,0} custom-call(%a.1, %b.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/transpose(jvp(ssm.scan))/ssm.scan/ssd_intra_bwd/pallas_call"}
  %fusion.1 = f32[8]{0} fusion(%v.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/ssm.scan/exp"}
  ROOT %dot.2 = f32[8,8]{1,0} dot(%a.1, %b.1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(train_step)/jvp()/while/body/closed_call/bsd,dk->bsk/dot_general"}
}
"""

OPS = [Op("%ssd_intra_fwd.1 = x", 0.0, 20.0),
       Op("%ssd_intra_bwd.1 = x", 20.0, 30.0),
       Op("%fusion.1 = x", 50.0, 10.0),
       Op("%dot.2 = x", 60.0, 40.0)]


def test_the_kernels_take_the_scope_and_the_kernel_class():
    classes = tr.hlo_classes(HLO)
    scope_of = scopes.hlo_scopes(HLO, (ssm_scan.SCOPE,))
    for name in ("ssd_intra_fwd.1", "ssd_intra_bwd.1"):
        assert classes[name] == "kernel"
        assert scope_of[name] == ssm_scan.SCOPE
    assert scope_of["dot.2"] == scopes.NONE
    s = ssm_scan.scan_seconds({0: OPS}, [Span("bench.window", 0, 100)],
                              classes, scope_of, "tpu")
    assert s == pytest.approx((20 + 30 + 10) * 1e-9)


def _trace_metrics(ctx, hlo, trace_dir, platform, reader):
    """Stands in for ``bench/run.py``'s frame that calls the readers."""
    return reader(ctx)


def _ctx():
    return {"steps": 2, "chips": 1, "cell": spec.load_cell(CELL),
            "peaks": V5E}


def test_kernel_pct_reads_the_kernels_share(monkeypatch):
    monkeypatch.setattr(tr, "read_xplane", lambda d: (
        {0: OPS}, [Span("bench.window", 0, 100)]))
    ctx = _ctx()
    pct = _trace_metrics(ctx, HLO, "/trace", "tpu",
                         spec.metric_reader("ssm.scan_kernel_pct"))
    assert pct == pytest.approx(100 * 50 / 60)
    ms = _trace_metrics(ctx, HLO, "/trace", "tpu",
                        spec.metric_reader("ssm.scan_ms_per_step"))
    assert ms == pytest.approx(1e3 * 60e-9 / 2)


@pytest.mark.parametrize("program", ["jnp-form", "unnamed"])
def test_kernel_pct_reads_nothing_without_kernels_or_scope(monkeypatch,
                                                           program):
    """The parent's program runs the scan in jnp: no kernel op in the
    scope.  A program without the name has no scan time at all."""
    hlo = (HLO.replace('custom_call_target="tpu_custom_call", ', "")
           if program == "jnp-form" else HLO.replace("ssm.scan", "mixer"))
    monkeypatch.setattr(tr, "read_xplane", lambda d: (
        {0: OPS}, [Span("bench.window", 0, 100)]))
    reader = spec.metric_reader("ssm.scan_kernel_pct")
    assert _trace_metrics(_ctx(), hlo, "/trace", "tpu", reader) is None
    assert reader({"steps": 1}) is None
