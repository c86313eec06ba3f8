"""From a profiler trace to device metrics.

The trace is the ``.xplane.pb`` that ``jax.profiler.trace`` writes, read
with ``jax.profiler.ProfileData``.  Each device plane's op line holds one
event per executed HLO operation; the host plane holds the benchmark's own
spans (``jax.profiler.TraceAnnotation``): ``bench.window`` around the traced
window and, inside it, ``bench.batch``, ``bench.dispatch`` and
``bench.fetch``.

Each device operation is put in one class by the run's compiled HLO text:

- ``collective``: all-to-all, all-gather, all-reduce, reduce-scatter and
  collective-permute, synchronous or async;
- ``kernel``: a Pallas kernel (a ``tpu_custom_call``), bare or fused;
- ``matmul``: an instruction that is, or fuses, a ``dot`` or
  ``convolution``;
- ``other``: everything else (elementwise, reductions, copies, RNG).

A ``while``, ``conditional`` or ``call`` is a container: its own event
spans the ops of its body, which the trace lists too, so its time is
counted in no class.

Busy time is the union of a device's op intervals inside the window;
exposed collective time is collective time during which no other op of the
class matmul, kernel or other runs on that device.  Both are averaged over
the devices.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

COLLECTIVES = ("all-to-all", "all-gather", "all-reduce", "reduce-scatter",
               "collective-permute")
MATMULS = ("dot", "convolution")
CONTAINERS = ("while", "conditional", "call")
CLASSES = ("matmul", "collective", "kernel", "other")
HOST_SPANS = ("bench.batch", "bench.dispatch", "bench.fetch")
WINDOW_SPAN = "bench.window"

_COMP = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"(?<![\w\-.%])([a-z][a-z0-9\-]*)\(")
_CALLS = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float


def hlo_classes(hlo_text: str) -> Dict[str, str]:
    """{instruction name: class} over every computation of the module."""
    own: Dict[str, str] = {}          # instruction -> class of its opcode
    calls: Dict[str, List[str]] = {}  # instruction -> called computations
    members: Dict[str, List[str]] = defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and comp is not None:
            name, rest = m.groups()
            op = _OPCODE.search(rest)
            opcode = op.group(1) if op else ""
            base = opcode.replace("-start", "").replace("-done", "")
            if base in COLLECTIVES:
                own[name] = "collective"
            elif opcode == "custom-call" and "tpu_custom_call" in rest:
                own[name] = "kernel"
            elif opcode in MATMULS:
                own[name] = "matmul"
            elif opcode in CONTAINERS:
                own[name] = "container"
            else:
                own[name] = "other"
            calls[name] = _CALLS.findall(rest)
            members[comp].append(name)
            continue
        c = _COMP.match(line)
        if c and "=" not in line.split("{")[0]:
            comp = c.group(1)

    rank = {"collective": 3, "kernel": 2, "matmul": 1, "other": 0,
            "container": -1}
    memo: Dict[str, str] = {}

    def comp_class(c: str, seen=()) -> str:
        best = "other"
        for ins in members.get(c, ()):
            k = cls(ins, seen + (c,))
            if rank[k] > rank[best]:
                best = k
        return best

    def cls(ins: str, seen=()) -> str:
        if ins in memo:
            return memo[ins]
        k = own[ins]
        if k == "other":
            for c in calls.get(ins, ()):
                if c in seen:
                    continue
                sub = comp_class(c, seen)
                if rank[sub] > rank[k]:
                    k = sub
        memo[ins] = k
        return k

    return {ins: cls(ins) for ins in own}


def short_name(name: str) -> str:
    """The instruction name of a trace event named by its HLO text."""
    return name.split(" ")[0].lstrip("%")


def classify(name: str, classes: Dict[str, str]) -> str:
    return classes.get(short_name(name), "other")


def _union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                   float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _clip(ops: Sequence[Op], t0: float, t1: float):
    return [(max(o.start_ns, t0), min(o.end_ns, t1)) for o in ops
            if o.end_ns > t0 and o.start_ns < t1]


def _minus(a, b):
    """Length of the union ``a`` outside the union ``b``."""
    total, j = 0.0, 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                total += b[k][0] - cur
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            total += e - cur
    return total


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float                     # averaged over devices
    class_s: Dict[str, float]         # device time per class, averaged
    exposed_collective_s: float       # averaged over devices
    device_ops: List[list]            # [[name, seconds]] top 10, summed
    idle_gaps: List[list]             # [[host activity, seconds]] top 10


def reduce(device_ops: Dict[int, List[Op]], host_spans: List[Span],
           classes: Dict[str, str], platform: str) -> Reduction:
    """Reduce one traced window.  Refuses any platform but a TPU: a CPU's
    op times are no device metric."""
    if platform != "tpu":
        raise ValueError(f"device metrics need a TPU trace, not {platform!r}")
    windows = [s for s in host_spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN} span, "
                         f"found {len(windows)}")
    t0, t1 = windows[0].start_ns, windows[0].end_ns
    n = max(len(device_ops), 1)
    busy = exposed = 0.0
    class_ns: Dict[str, float] = defaultdict(float)
    per_name: Dict[str, float] = defaultdict(float)
    first_busy = None
    for dev in sorted(device_ops):
        ops = device_ops[dev]
        by_cls: Dict[str, List[Op]] = defaultdict(list)
        for o in ops:
            by_cls[classify(o.name, classes)].append(o)
        all_u = _union(_clip(ops, t0, t1))
        if first_busy is None:
            first_busy = all_u
        busy += _length(all_u)
        for c, lst in by_cls.items():
            if c == "container":
                continue
            clipped = _clip(lst, t0, t1)
            class_ns[c] += sum(e - s for s, e in clipped)
            for o, (s, e) in zip([o for o in lst if o.end_ns > t0
                                  and o.start_ns < t1], clipped):
                per_name[f"{short_name(o.name)} [{c}]"] += e - s
        coll = _union(_clip(by_cls.get("collective", []), t0, t1))
        rest = _union(_clip([o for c in ("matmul", "kernel", "other")
                             for o in by_cls.get(c, [])], t0, t1))
        exposed += _minus(coll, rest)
    top = sorted(per_name.items(), key=lambda kv: -kv[1])[:10]
    return Reduction(
        window_s=(t1 - t0) * 1e-9,
        busy_s=busy / n * 1e-9,
        class_s={c: class_ns.get(c, 0.0) / n * 1e-9 for c in CLASSES},
        exposed_collective_s=exposed / n * 1e-9,
        device_ops=[[k, v / n * 1e-9] for k, v in top],
        idle_gaps=_idle_gaps(first_busy or [], host_spans, t0, t1))


def _idle_gaps(busy, host_spans, t0, t1) -> List[list]:
    """The longest gaps of the first device, each named by the host span
    that covers most of it."""
    gaps, cur = [], t0
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    spans = [s for s in host_spans if s.name in HOST_SPANS]
    named = []
    for gs, ge in gaps:
        cover: Dict[str, float] = defaultdict(float)
        for sp in spans:
            ov = min(ge, sp.end_ns) - max(gs, sp.start_ns)
            if ov > 0:
                cover[sp.name] += ov
        what = max(cover, key=cover.get) if cover else "host.other"
        named.append([what, (ge - gs) * 1e-9])
    return sorted(named, key=lambda g: -g[1])[:10]


# ---------------------------------------------------------------------------
# Reading the profiler's file.
# ---------------------------------------------------------------------------

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINES = ("XLA Ops",)


def read_xplane(trace_dir: str):
    """({device id: [Op]}, [Span]) from the one ``.xplane.pb`` under
    ``trace_dir``."""
    import jax

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"expected one .xplane.pb under {trace_dir}, "
                         f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    device_ops: Dict[int, List[Op]] = {}
    spans: List[Span] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops += [Op(e.name, e.start_ns, e.duration_ns)
                            for e in line.events]
            device_ops[int(m.group(1))] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append(Span(e.name, e.start_ns,
                                          e.start_ns + e.duration_ns))
    return device_ops, spans
