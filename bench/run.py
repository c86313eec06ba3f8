#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Set-up builds the cell's configuration and the program's jitted train step
(as ``repro.launch.train`` builds it), makes the weights and the first
batches on the device from the seed, and drives the step through its first
three steps: the steps that ``correct`` compares with the plain reference.
That compiles (or loads from the persistent cache) every program the window
runs.  The window then runs whole training steps for ``--seconds``, the
CLI's loop: one dispatch per step, the losses fetched every 10 steps.

With ``--trace 0`` the result line carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics from a profiler trace of the
window.  After the window the program's state is freed and the reference
runs; each compared number is printed beside its limit, last on standard
error and last in the result line.

The command exits nonzero, and prints no result, where JAX finds no TPU or
fewer chips than the cell asks for, or where the program is absent.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import gc
import json
import math
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = ROOT / ".jax_cache"
FETCH_EVERY = 10
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _fail(msg: str, code: int) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return code


class CompileCounter:
    """Counts backend compilations (and cache loads) while armed."""

    def __init__(self):
        import jax
        self.armed, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, *_, **__):
        if self.armed and name == COMPILE_EVENT:
            self.count += 1


def main(argv=None, rehearse: bool = False) -> int:
    """``rehearse=True`` (tests only) runs on the CPU at smoke widths and
    reports no device metric."""
    args = _args(argv)
    # the persistent compile cache lives at a fixed path in the checkout;
    # the program takes this directory too (repro.device.enable_compile_cache)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)

    import jax

    from bench import correct as correct_lib
    from bench import inputs, spec

    if not rehearse:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.config.update("jax_compilation_cache_max_size", -1)
    cell = spec.load_cell(args.workload)
    if rehearse:
        cell = spec.smoke(cell)
    devs = jax.devices()
    platform = devs[0].platform
    if not rehearse and platform != "tpu":
        return _fail(f"no TPU: JAX found {len(devs)} {platform} device(s)", 3)
    if len(devs) < cell.chips:
        return _fail(f"{cell.name} needs {cell.chips} chips, JAX found "
                     f"{len(devs)}", 3)
    devices = devs[:cell.chips] if not rehearse else devs[:1]
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        return _fail(f"the program is not in this checkout: {e}", 4)
    from bench import program as program_lib
    peaks = None if rehearse else spec.peaks(devs[0].device_kind)

    counter = CompileCounter()
    prog = program_lib.build(cell, devices, smoke=rehearse)
    key = inputs.root_key(args.seed)
    bkey = jax.random.fold_in(key, correct_lib.BATCH_STREAM)
    state, prog_readings = correct_lib.program_readings(prog, key)

    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if args.trace else None
    setup_s = time.perf_counter() - T_START
    counter.armed = True
    window = _window(prog, state, bkey, args.seconds, trace_dir)
    counter.armed = False
    state = window.pop("state")
    peak = _peak_bytes(devices)

    extra = {}
    if args.trace and not rehearse:
        extra = _trace_metrics(prog, cell, state, window, trace_dir,
                               platform, peaks)
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    del state
    gc.collect()

    ref = correct_lib.reference_readings(
        cell, program_lib.model_dict(prog.cfg), key, device=devices[0])
    numbers = correct_lib.compare(prog_readings, ref)
    limits = cell.limits["smoke" if rehearse else "chip"]
    failed = sum(1 for x in window["losses"] if not math.isfinite(x))
    ok = correct_lib.judge(numbers, limits) and failed == 0

    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    steps, secs = window["steps"], window["seconds"]
    tokens_s = steps * cell.tokens_per_step / secs
    print(f"window: {steps} steps in {secs:.6f} s, {counter.count} "
          f"compilations in the window", flush=True)
    print(f"setup_s {setup_s:.6f}; readings {json.dumps(prog_readings)}",
          flush=True)
    if rehearse:
        metrics_out = {}          # a CPU run reports no device metric
    elif args.trace:
        metrics_out = {k: v for k, v in extra.pop("metrics").items()}
        device.update(extra.pop("device"))
    else:
        metrics_out = {
            "train_tokens_per_s": {"value": tokens_s, "unit": "tokens/s"},
            "peak_hbm_gib": {"value": peak / 2 ** 30, "unit": "GiB"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        metrics_out = {k: v for k, v in metrics_out.items()
                       if k in {m["name"] for m in cell.end_to_end}}
    checks = correct_lib.report(numbers, limits)
    checks["window_nonfinite_losses"] = [failed, 0]
    print(f"worst leaves: grad {numbers['grad_leaf']}, delta "
          f"{numbers['delta_leaf']}", file=sys.stderr)
    for name, (value, limit) in checks.items():
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    sys.stderr.flush()
    result = {"correct": bool(ok), "attempted": steps, "failed": failed,
              "metrics": metrics_out, "device": device}
    if extra.get("breakdown"):
        result["breakdown"] = extra["breakdown"]
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


def _window(prog, state, bkey, seconds, trace_dir):
    """The measured window: whole steps until ``seconds`` have passed, then
    the wait for the last one.  With ``trace_dir`` it runs under the
    profiler."""
    import jax

    from bench.correct import STEPS

    if trace_dir:
        jax.profiler.start_trace(trace_dir)
    span = jax.profiler.TraceAnnotation
    losses, pending, steps = [], [], 0
    with span("bench.window"):
        t0 = time.perf_counter()
        while True:
            with span("bench.batch"):
                batch = prog.make_batch(bkey, STEPS + steps)
            with span("bench.dispatch"):
                state, metrics = prog.step(state, batch)
            pending.append(metrics["loss"])
            steps += 1
            if steps % FETCH_EVERY == 0:
                with span("bench.fetch"):
                    losses += [float(x) for x in jax.device_get(pending)]
                pending = []
            if time.perf_counter() - t0 >= seconds:
                break
        with span("bench.fetch"):
            jax.block_until_ready(state)
            losses += [float(x) for x in jax.device_get(pending)]
        t1 = time.perf_counter()
    if trace_dir:
        jax.profiler.stop_trace()
    return {"state": state, "steps": steps, "seconds": t1 - t0,
            "losses": losses, "batch": batch}


def _peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def _trace_metrics(prog, cell, state, window, trace_dir, platform, peaks):
    """Per-layer metrics of the traced window, by each metric's reader."""
    from bench import flops, spec, trace_reduce

    traced = prog.step.trace(state, window["batch"])
    hlo = traced.lower().compile().as_text()
    red = trace_reduce.reduce(*trace_reduce.read_xplane(trace_dir),
                              trace_reduce.hlo_classes(hlo), platform)
    cfg_d = dict(cell.config["model"])
    t = cell.traffic
    ctx = {
        "cell": cell, "steps": window["steps"],
        "window_s": window["seconds"], "chips": cell.chips, "peaks": peaks,
        "reduction": red,
        "model_flops_per_step": flops.model_flops_per_step(
            cfg_d, t["global_batch"], t["seq"]),
        "matmul_flops_per_step_per_chip": flops.jaxpr_matmul_flops(
            traced.jaxpr),
    }
    metrics = {}
    for m in cell.per_layer:
        value = spec.metric_reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return {"metrics": metrics,
            "device": {"busy_s": red.busy_s, "window_s": red.window_s},
            "breakdown": {"device_ops": red.device_ops,
                          "idle_gaps": red.idle_gaps}}


if __name__ == "__main__":
    sys.exit(main())
