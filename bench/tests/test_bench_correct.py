"""``correct`` at smoke widths: the sound program passes the cell's smoke
limits, and the control and each planted fault fail them."""

import jax
import pytest

from bench import correct, inputs, program, spec

CELL = "internvl2-26b-l1.train-dps"
SEED = 424242


@pytest.fixture(scope="module")
def setup():
    cell = spec.smoke(spec.load_cell(CELL))
    prog = program.build(cell, jax.devices()[:1], smoke=True)
    cfg_d = program.model_dict(prog.cfg)
    key = inputs.root_key(SEED)
    ref = correct.reference_readings(cell, cfg_d, key)
    return cell, prog, cfg_d, key, ref


def _numbers(setup, fault):
    cell, prog, cfg_d, key, ref = setup
    if fault == "control":
        got = correct.reference_readings(cell, cfg_d, key, precision="fp8",
                                         stream=correct.CONTROL_STREAM)
    else:
        _, got = correct.program_readings(prog, key, fault)
    return correct.compare(got, ref), cell.limits["smoke"]


def test_sound_program_is_correct(setup):
    numbers, limits = _numbers(setup, "none")
    assert correct.judge(numbers, limits), numbers


@pytest.mark.parametrize("fault", ["control", "half", "unchanged"])
def test_control_and_faults_are_not_correct(setup, fault):
    numbers, limits = _numbers(setup, fault)
    assert not correct.judge(numbers, limits), numbers


def test_mamba2_reference_agrees_with_the_program_at_smoke_widths():
    """The Mamba2 reference waits for its cell (the program's SSD scan
    overflows at the published chunk of 256, see PERF.md); at the smoke
    chunk of 8 both sides are finite and agree.  The bounds are twice the
    largest gaps of 12 seeds on the CPU (4.7e-4, 1.5e-2, 3.3e-2)."""
    from bench.tests.test_bench_flops import smoke_cell
    cell = smoke_cell("mamba2-1.3b-l16")
    prog = program.build(cell, jax.devices()[:1], smoke=True)
    key = inputs.root_key(SEED)
    _, got = correct.program_readings(prog, key)
    ref = correct.reference_readings(cell, program.model_dict(prog.cfg), key)
    numbers = correct.compare(got, ref)
    assert numbers["loss_gap"] < 1e-3, numbers
    assert numbers["grad_norm_gap"] < 3e-2, numbers
    assert numbers["delta_norm_gap"] < 6.6e-2, numbers
