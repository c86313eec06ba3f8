"""Device time of the program's named passes, from a traced window.

The program names its passes with ``jax.named_scope``.  A name reaches the
compiled HLO as a segment of each instruction's ``op_name`` metadata
(``jit(train_step)/jvp()/while/body/closed_call/dps.acts/mul``), under
``jax.checkpoint`` recomputation and ``custom_vjp`` rules too.  The names
below are the benchmark's contract with the program; they are not imported
from it, so a program that lacks them reads as all ``"none"``.

A fusion's own ``op_name`` is only its root instruction's, and a fusion can
take in the instructions of several passes.  So an instruction that calls a
computation (``calls=``) takes the scope that most of that computation's
instructions carry; parameters, constants and tuples do not vote, an
unscoped instruction votes ``"none"``, and a tie goes to ``"none"``.

Only class ``other`` ops (``trace_reduce.hlo_classes``) are split, so the
scopes' seconds and ``"none"`` add up to ``Reduction.class_s["other"]``,
and no matmul time is ever given to a scope.
"""

from __future__ import annotations

import re
import sys
from collections import Counter, defaultdict
from typing import Dict, List, Optional, Sequence

from bench import trace_reduce as tr

SCOPES = ("dps.weights", "dps.acts", "dps.grads", "optim")
NONE = "none"
NO_VOTE = ("parameter", "constant", "tuple")

_OP_NAME = re.compile(r'metadata=\{[^}]*\bop_name="([^"]*)"')
_CALLED = re.compile(r"\bcalls=%?([\w.\-]+)")


def _innermost(op_name: str, names: Sequence[str]) -> str:
    for seg in reversed(op_name.split("/")):
        if seg in names:
            return seg
    return NONE


def hlo_scopes(hlo_text: str, names: Sequence[str] = SCOPES
               ) -> Dict[str, str]:
    """{instruction name: scope in ``names`` | "none"} over every
    computation of the module."""
    own: Dict[str, str] = {}
    opcode: Dict[str, str] = {}
    calls: Dict[str, List[str]] = {}
    members: Dict[str, List[str]] = defaultdict(list)
    comp = None
    for line in hlo_text.splitlines():
        m = tr._INSTR.match(line)
        if m and comp is not None:
            name, rest = m.groups()
            op = tr._OPCODE.search(rest)
            opcode[name] = op.group(1) if op else ""
            o = _OP_NAME.search(rest)
            own[name] = _innermost(o.group(1), names) if o else NONE
            calls[name] = _CALLED.findall(rest)
            members[comp].append(name)
            continue
        # a header's parameter list may hold ``/*index=5*/`` comments, so
        # a header is told from an instruction by the missing ``name =``
        c = tr._COMP.match(line)
        if c and not m:
            comp = c.group(1)

    memo: Dict[str, str] = {}

    def scope(ins: str, seen=()) -> str:
        if ins in memo:
            return memo[ins]
        if not calls[ins]:
            memo[ins] = own[ins]
            return own[ins]
        votes: Counter = Counter()
        for c in calls[ins]:
            if c in seen:
                continue
            for sub in members.get(c, ()):
                if opcode[sub] not in NO_VOTE:
                    votes[scope(sub, seen + (c,))] += 1
        top = votes.most_common(2)
        won = (top[0][0] if top and (len(top) == 1 or top[0][1] > top[1][1])
               else NONE)
        memo[ins] = won
        return won

    return {ins: scope(ins) for ins in own}


def scope_seconds(device_ops: Dict[int, List[tr.Op]],
                  host_spans: List[tr.Span], classes: Dict[str, str],
                  scopes: Dict[str, str], platform: str) -> Dict[str, float]:
    """{scope | "none": device seconds} of the class ``other`` ops inside
    the window, averaged over devices, as ``trace_reduce.reduce`` averages
    ``class_s``."""
    if platform != "tpu":
        raise ValueError(f"device metrics need a TPU trace, not {platform!r}")
    windows = [s for s in host_spans if s.name == tr.WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"expected one {tr.WINDOW_SPAN} span, "
                         f"found {len(windows)}")
    t0, t1 = windows[0].start_ns, windows[0].end_ns
    total = dict.fromkeys((*SCOPES, NONE), 0.0)
    for ops in device_ops.values():
        for o in ops:
            if (o.end_ns <= t0 or o.start_ns >= t1
                    or tr.classify(o.name, classes) != "other"):
                continue
            s = scopes.get(tr.short_name(o.name), NONE)
            total[s] = (total.get(s, 0.0)
                        + min(o.end_ns, t1) - max(o.start_ns, t0))
    n = max(len(device_ops), 1)
    return {k: v / n * 1e-9 for k, v in total.items()}


def _harness_locals(ctx) -> Optional[dict]:
    """The locals of the harness frame that built ``ctx``.

    ``bench/run.py``'s ``_trace_metrics`` holds the compiled step's text
    (``hlo``), the trace directory (``trace_dir``) and the ``platform``
    there and hands its readers only ``ctx``; the frame is recognised by
    holding this very ``ctx``."""
    f = sys._getframe(1)
    while f is not None:
        loc = f.f_locals
        if loc.get("ctx") is ctx and {"hlo", "trace_dir",
                                      "platform"} <= loc.keys():
            return loc
        f = f.f_back
    return None


def seconds(ctx) -> Dict[str, float]:
    """``scope_seconds`` of the traced window, read once per run and kept
    in ``ctx["scope_s"]``; empty where the harness frame is not found."""
    if "scope_s" not in ctx:
        h = _harness_locals(ctx)
        if h is None:
            ctx["scope_s"] = {}
        else:
            ops, spans = tr.read_xplane(h["trace_dir"])
            ctx["scope_s"] = scope_seconds(
                ops, spans, tr.hlo_classes(h["hlo"]), hlo_scopes(h["hlo"]),
                h["platform"])
    return ctx["scope_s"]


def ms_per_step(ctx, scope: str) -> Optional[float]:
    """Device milliseconds per step of ``scope``; None where it has none."""
    s = seconds(ctx).get(scope, 0.0)
    return 1e3 * s / ctx["steps"] if s > 0 else None
