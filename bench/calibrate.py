#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, one cell at a time.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        --what program|control|half|unchanged [--smoke]

For each seed it prints one JSON line with the three compared numbers
(``bench/correct.py``) of

- ``program``: the program's first three steps against the reference;
- ``control``: the reference computed with float8 matmul operands, on a
  rounding stream of its own, put in the program's place;
- ``half``: the program with half of each batch left out of the loss;
- ``unchanged``: the program with a step that returns its state unchanged.

The program and the references are built once and reused across seeds.  The
lines also go to ``chiprun_out/calibrate/<cell>[.smoke].<what>.jsonl``.  With
``--smoke`` it runs on the CPU at the smoke widths.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def readings(cell, smoke: bool, seeds, what: str):
    import jax

    from bench import correct, inputs, program, spec

    devices = jax.devices()[:cell.chips]
    if smoke:
        cell = spec.smoke(cell)
    prog = None
    if what in ("program", "half", "unchanged"):
        prog = program.build(cell, devices, smoke=smoke)
    cfg_d = program.model_dict(program.model_config(cell, smoke))
    for seed in seeds:
        key = inputs.root_key(seed)
        if prog is not None:
            state, got = correct.program_readings(
                prog, key, "none" if what == "program" else what)
            del state
            gc.collect()
        else:
            got = correct.reference_readings(
                cell, cfg_d, key, device=devices[0],
                precision="fp8", stream=correct.CONTROL_STREAM)
        ref = correct.reference_readings(cell, cfg_d, key, device=devices[0])
        yield {"seed": seed, "what": what, **correct.compare(got, ref),
               "losses": got["losses"], "ref_losses": ref["losses"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--what", required=True,
                    choices=("program", "control", "half", "unchanged"))
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import spec

    cell = spec.load_cell(args.workload)
    out_dir = ROOT / "chiprun_out" / "calibrate"
    out_dir.mkdir(parents=True, exist_ok=True)
    seeds = [int(s) for s in args.seeds.split(",")]
    size = ".smoke" if args.smoke else ""
    with open(out_dir / f"{cell.name}{size}.{args.what}.jsonl", "a") as f:
        for line in readings(cell, args.smoke, seeds, args.what):
            print(json.dumps(line), flush=True)
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
