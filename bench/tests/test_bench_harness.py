"""The harness end to end at smoke widths on the CPU, and its refusals."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

from bench import run, spec

ROOT = Path(__file__).resolve().parents[2]
CELL = "internvl2-26b-l1.train-dps"


def _last_json(text):
    lines = [l for l in text.splitlines() if l.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def test_one_cell_end_to_end_on_the_cpu(capsys):
    rc = run.main(["--workload", CELL, "--seed", str(2 ** 31 + 12345),
                   "--seconds", "0.3", "--trace", "0"], rehearse=True)
    out = capsys.readouterr()
    assert rc == 0
    last = out.out.strip().splitlines()[-1]
    result = json.loads(last)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}          # no device metric off the chip
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["correct"] is True
    limits = spec.load_cell(CELL).limits["smoke"]
    assert set(limits) <= set(result["checks"])
    tail = out.err.strip().splitlines()[-len(result["checks"]):]
    assert all(l.startswith("check ") for l in tail)
    assert "0 compilations in the window" in out.out


def test_no_tpu_means_no_result(capsys):
    rc = run.main(["--workload", CELL, "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert _last_json(out.out) is None
    assert "no TPU" in out.err


def test_without_the_program_there_is_no_result(tmp_path):
    """A checkout of BENCHMARK.json and bench/ alone holds no system under
    test: the run fails before it reports anything."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    code = ("import sys; sys.path.insert(0, '.'); from bench import run; "
            f"sys.exit(run.main(['--workload', '{CELL}', '--seed', '1', "
            "'--seconds', '1', '--trace', '0'], rehearse=True))")
    p = subprocess.run([sys.executable, "-c", code], cwd=tmp_path, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert _last_json(p.stdout) is None
    assert "program is not in this checkout" in p.stderr
