"""The SSD chunked scan of the Mamba2 cells: its device time from a traced
window, and its roofline from the cell's shapes.

The program runs the scan's body (the intra-chunk quadratic form, the
chunk states, the recurrence over chunks and the states' contribution to
each position) under ``jax.named_scope("ssm.scan")``; the input and output
projections, the convolution and the gated norm are outside it.  An op
takes the scope by ``scopes.hlo_scopes``' majority rule.  The scan's
einsums fuse into matmul-class ops, so every class counts here (a
container's own event spans its body's ops and is left out), unlike
``scopes.scope_seconds``, which splits class ``other`` only.  A program
without the name reads nothing.

The roofline counts the work the scan needs from the shapes alone, so it
reads the same whatever implements the scan.  Per layer, per row:

- operations: the SSD terms of ``flops.model_flops_per_step``, 2 per
  multiply-add: C Bᵀ inside each chunk and the decay-masked product with
  x at their causal half (Q N and Q H P per position), the chunk states
  and the states' contribution to the outputs (2 N H P each);
- bytes: the scan's inputs x·dt, B and C in the configuration's ``dtype``
  and its log-decays in float32, its output y in ``dtype``, and the chunk
  states, float32 (H P N per chunk): each read or written once; nothing
  of size Q x Q.

The forward, the recomputed forward and the backward (twice the forward)
make four passes of each.  The roofline time is the larger of operations
over the bf16 peak and bytes over the HBM peak.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import jax.numpy as jnp

from bench import scopes
from bench import trace_reduce as tr

SCOPE = "ssm.scan"
PASSES = 4          # forward, recomputed forward, backward at twice
F32 = 4


def _shapes(c: dict, rows: int, seq: int):
    di = c["ssm_expand"] * c["d_model"]
    P, N = c["ssm_head_dim"], c["ssm_state"]
    Q = min(c["ssm_chunk"], seq)
    return rows * seq, di // P, P, N, Q, -(-seq // Q) * rows


def flops_per_step(c: dict, rows: int, seq: int) -> float:
    """The scan's operations in one training step of ``rows`` x ``seq``."""
    pos, H, P, N, Q, _ = _shapes(c, rows, seq)
    per_pos = Q * N + Q * H * P + 4 * N * H * P
    return float(PASSES * c["n_layers"] * per_pos * pos)


def bytes_per_step(c: dict, rows: int, seq: int) -> float:
    """The scan's HBM bytes in one training step of ``rows`` x ``seq``."""
    pos, H, P, N, Q, chunks = _shapes(c, rows, seq)
    act = jnp.dtype(c["dtype"]).itemsize
    per_pass = (pos * (2 * H * P + 2 * N) * act     # x·dt and y; B and C
                + pos * H * F32                       # log-decays
                + chunks * H * P * N * F32)           # chunk states
    return float(PASSES * c["n_layers"] * per_pass)


def roofline_s(c: dict, rows: int, seq: int, peaks: Dict[str, float]
               ) -> float:
    return max(flops_per_step(c, rows, seq) / peaks["bf16_flops"],
               bytes_per_step(c, rows, seq) / peaks["hbm_bytes_per_s"])


def scan_seconds(device_ops: Dict[int, List[tr.Op]],
                 host_spans: List[tr.Span], classes: Dict[str, str],
                 scope_of: Dict[str, str], platform: str) -> float:
    """Device seconds of the ops of scope ``ssm.scan`` inside the window,
    of any class but a container, averaged over devices."""
    if platform != "tpu":
        raise ValueError(f"device metrics need a TPU trace, not {platform!r}")
    windows = [s for s in host_spans if s.name == tr.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {tr.WINDOW_SPAN} span, "
                         f"found {len(windows)}")
    t0, t1 = windows[0].start_ns, windows[0].end_ns
    total = 0.0
    for ops in device_ops.values():
        for o in ops:
            short = tr.short_name(o.name)
            if (o.end_ns <= t0 or o.start_ns >= t1
                    or scope_of.get(short) != SCOPE
                    or classes.get(short) == "container"):
                continue
            total += min(o.end_ns, t1) - max(o.start_ns, t0)
    return total / max(len(device_ops), 1) * 1e-9


def seconds(ctx) -> float:
    """``scan_seconds`` of the traced window, read once per run and kept in
    ``ctx["ssm_scan_s"]``; 0 where the harness frame is not found."""
    if "ssm_scan_s" not in ctx:
        h = scopes._harness_locals(ctx)
        if h is None:
            ctx["ssm_scan_s"] = 0.0
        else:
            ops, spans = tr.read_xplane(h["trace_dir"])
            ctx["ssm_scan_s"] = scan_seconds(
                ops, spans, tr.hlo_classes(h["hlo"]),
                scopes.hlo_scopes(h["hlo"], (SCOPE,)), h["platform"])
    return ctx["ssm_scan_s"]


def ms_per_step(ctx) -> Optional[float]:
    """Device milliseconds per step of the scan; None where it has none."""
    s = seconds(ctx)
    return 1e3 * s / ctx["steps"] if s > 0 else None


def roofline_pct(ctx) -> Optional[float]:
    """The scan's roofline time over its device time, in %: one chip's
    share of the cell's rows."""
    s = seconds(ctx)
    if s <= 0:
        return None
    t = ctx["cell"].traffic
    rows = t["global_batch"] // ctx["chips"]
    best = roofline_s(ctx["cell"].config["model"], rows, t["seq"],
                      ctx["peaks"])
    return 100.0 * best / (s / ctx["steps"])
