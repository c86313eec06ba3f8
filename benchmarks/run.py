"""Benchmark aggregator: one bench per paper artifact.

  PYTHONPATH=src python -m benchmarks.run            # quick mode
  BENCH_QUICK=0 PYTHONPATH=src python -m benchmarks.run   # paper-scale

The MNIST-class benches reproduce the paper's own evaluation (Figs. 3-4,
Table 1, the Gupta rounding comparison); bench_quant covers the kernel
hot-spot.  Device timings and roofline shares come from ``bench/run.py``
on the chip, not from here.
"""

from __future__ import annotations

import json
import sys
import time
import traceback


def main():
    from benchmarks import (bench_bitwidths, bench_collectives,
                            bench_convergence, bench_quant, bench_rounding,
                            bench_schemes, bench_zero)
    suites = [
        ("convergence (paper Fig. 4)", bench_convergence.run),
        ("bitwidths (paper Fig. 3)", bench_bitwidths.run),
        ("rounding (Gupta comparison)", bench_rounding.run),
        ("schemes (paper Table 1)", bench_schemes.run),
        ("quantizer hot-spot", bench_quant.run),
        ("collectives (int8 gradient wire)", bench_collectives.run),
        ("ZeRO-1 (sharded optimizer + int8 wire)", bench_zero.run),
    ]
    failures = []
    for name, fn in suites:
        t0 = time.time()
        print(f"=== {name} ===", flush=True)
        try:
            out = fn()
            claims = out.get("claims")
            if claims is not None:
                print(json.dumps(claims, indent=1))
                if not all(claims.values()):
                    failures.append((name, claims))
            print(f"  ({time.time() - t0:.1f}s)", flush=True)
        except Exception:
            traceback.print_exc()
            failures.append((name, "exception"))
    if failures:
        print("\nFAILED CLAIMS/SUITES:")
        for n, c in failures:
            print(" -", n, c)
        sys.exit(1)
    print("\nall benchmark claims hold")


if __name__ == "__main__":
    main()
