"""Optimized-HLO collective accounting shared by dryrun, benchmarks, tests.

Two views of the same parse:

* :func:`collective_bytes` — per-collective-type payload bytes (the
  dry-run's historical metric; kept for the roofline JSON schema).
* :func:`collective_wire_bytes` — per-(op, dtype) **wire** bytes under the
  ring-transfer model: an all-reduce moves ~2× its payload over the
  interconnect (reduce-scatter + all-gather phases), the other collectives
  ~1×.  This is the honest way to compare an fp32 gradient all-reduce
  against the compressed int8 two-leg path (all-to-all + all-gather), and
  what the ``grad_allreduce_bits`` regression test asserts on.

Every byte count here flows through ONE instruction-walker
(:func:`_instructions`): each consumer names the opcodes it cares about
and interprets the parsed shapes; there is a single place that decides
what an "instruction line" is.  ``repro.analysis.hlo_audit`` builds its
rule engine on the same walker.
"""

from __future__ import annotations

import re
from typing import Dict, Iterable, Iterator, List, NamedTuple, Tuple

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([\d,]*)\]")
_ASSIGN_RE = re.compile(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(.*)")
# replica groups spelled out ({{0,1,..},..}) or in iota form ([G,N]<=[..])
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([\d,]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[\d+,(\d+)\]")
_DTYPE_BYTES = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "s8": 1,
                "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f64": 8, "s16": 2,
                "u16": 2, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3": 1,
                "f8e4m3fnuz": 1, "f8e5m2fnuz": 1, "f8e4m3b11fnuz": 1,
                "c64": 8, "c128": 16}

# interconnect traversals per payload byte under the ring model
_RING_FACTOR = {"all-reduce": 2.0, "all-gather": 1.0, "reduce-scatter": 1.0,
                "all-to-all": 1.0, "collective-permute": 1.0}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    try:
        return n * _DTYPE_BYTES[dtype]
    except KeyError:
        raise ValueError(
            f"unknown HLO dtype {dtype!r} in shape {dtype}[{dims}] — add "
            f"it to repro.launch.hlo_stats._DTYPE_BYTES (guessing a byte "
            f"width would silently corrupt the wire accounting)") from None


class Instruction(NamedTuple):
    """One parsed assignment line whose opcode matched the walker filter.

    ``shapes`` holds every ``(dtype, bytes)`` on the line (result AND any
    spelled-out operand shapes); ``result_shapes`` only those left of the
    opcode token (the result side — a tuple result contributes one entry
    per element).
    """

    op: str
    shapes: Tuple[Tuple[str, int], ...]
    result_shapes: Tuple[Tuple[str, int], ...]
    line: str


def _instructions(hlo_text: str, op_names: Iterable[str]
                  ) -> Iterator[Instruction]:
    """The ONE instruction-walker: yield every assignment whose opcode is
    in ``op_names``.

    Matches ``name = ... <op>(...)`` (``ROOT``-prefixed too) including
    inside fusion/while/branch computation bodies; ``<op>-start`` variants
    count, ``<op>-done`` completions are skipped (their payload was
    already counted at the ``-start``).
    """
    pats = [(op, re.compile(rf"\b{re.escape(op)}(-start|-done)?\("))
            for op in op_names]
    for line in hlo_text.splitlines():
        s = line.strip()
        m = _ASSIGN_RE.match(s)
        if not m:
            continue
        rest = m.group(1)
        for op, pat in pats:
            tok = pat.search(rest)
            if tok is None:
                continue
            if f"{op}-done" in rest:
                break
            shapes = tuple((d, _shape_bytes(d, dims))
                           for d, dims in _SHAPE_RE.findall(rest))
            result = tuple(
                (d, _shape_bytes(d, dims))
                for d, dims in _SHAPE_RE.findall(rest[:tok.start()]))
            yield Instruction(op, shapes, result, s)
            break


def _group_size(line: str) -> int:
    """Ranks per replica group of a collective line (1 when unstated)."""
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([r for r in m.group(1).split(",") if r])
    m = _GROUPS_IOTA_RE.search(line)
    return int(m.group(1)) if m else 1


def _collective_instructions(hlo_text: str):
    """Yield ``(op, dtype, payload_bytes)`` per collective result.

    Payload = the full-tensor side of the collective.  Current HLO text
    prints operands by name only, so only the result shapes are on the
    line.  Each element of a result contributes its own bytes under its own
    dtype: a combined ``(f32[a], f32[b]) all-reduce`` carries both
    tensors, and the CPU lowering of ``all-to-all`` returns one
    ``s8[1,c]`` chunk per rank.  A ``reduce-scatter`` result is one rank's
    shard, so its payload is the result times the replica-group size.  An
    async ``-start`` result also holds the operand, so there the largest
    shape stands for the payload.
    """
    for ins in _instructions(hlo_text, COLLECTIVE_OPS):
        if not ins.shapes:
            continue
        if not ins.result_shapes or f"{ins.op}-start(" in ins.line:
            dtype, nbytes = max(ins.shapes, key=lambda t: t[1])
            yield ins.op, dtype, float(nbytes)
            continue
        mult = _group_size(ins.line) if ins.op == "reduce-scatter" else 1
        for dtype, nbytes in ins.result_shapes:
            yield ins.op, dtype, float(nbytes * mult)


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-collective-type payload bytes from optimized HLO (the
    ring-transfer approximation)."""
    out = {k: 0.0 for k in COLLECTIVE_OPS}
    counts = {k: 0 for k in COLLECTIVE_OPS}
    for op, _, nbytes in _collective_instructions(hlo_text):
        out[op] += nbytes
        counts[op] += 1
    out["counts"] = counts
    return out


def collective_wire_bytes(hlo_text: str) -> Dict[str, object]:
    """Ring-model wire bytes per (op, dtype) plus totals.

    Returns ``{"by_op_dtype": {op: {dtype: bytes}}, "total": float,
    "by_dtype": {dtype: bytes}}`` where every instruction contributes
    ``ring_factor(op) × payload bytes`` (see
    :func:`_collective_instructions`) under its payload dtype.
    """
    by_op: Dict[str, Dict[str, float]] = {}
    by_dtype: Dict[str, float] = {}
    total = 0.0
    for op, dtype, nbytes in _collective_instructions(hlo_text):
        wire = _RING_FACTOR[op] * nbytes
        by_op.setdefault(op, {})
        by_op[op][dtype] = by_op[op].get(dtype, 0.0) + wire
        by_dtype[dtype] = by_dtype.get(dtype, 0.0) + wire
        total += wire
    return {"by_op_dtype": by_op, "by_dtype": by_dtype, "total": total}


def op_bytes(hlo_text: str, op_name: str) -> Dict[str, object]:
    """Result bytes of every ``op_name`` instruction, split by dtype.

    Parses optimized HLO (fusion bodies included) for lines of the form
    ``%x = <dtype>[dims] <op_name>(...)`` and sums the result-shape bytes
    per dtype.  Returns ``{"by_dtype": {dtype: bytes}, "total": float,
    "count": int}``.  The headline consumer is the no-fp32-flat-concat
    guarantee of the rebuilt ``dps_allreduce_mean_tree``: a compiled tree
    all-reduce must show (near-)zero ``f32`` ``concatenate`` bytes — the
    leaves are encoded straight into the preallocated int8 wire buffer.
    """
    by_dtype: Dict[str, float] = {}
    count = 0
    for ins in _instructions(hlo_text, (op_name,)):
        if not ins.result_shapes:
            continue
        dtype, nbytes = ins.result_shapes[0]
        by_dtype[dtype] = by_dtype.get(dtype, 0.0) + nbytes
        count += 1
    return {"by_dtype": by_dtype,
            "total": float(sum(by_dtype.values())), "count": count}


def concat_bytes(hlo_text: str) -> Dict[str, object]:
    """:func:`op_bytes` for ``concatenate`` — the fp32 flat-concat probe."""
    return op_bytes(hlo_text, "concatenate")


def wire_bytes_summary(hlo_text: str) -> Dict[str, float]:
    """Compact int8-vs-fp32 view of :func:`collective_wire_bytes`.

    The headline accounting for the compressed collective schedules
    (``grad_allreduce_bits`` / ``zero_opt_shards``): how many ring-model
    wire bytes ride the int8 payload vs fp32, and the int8 fraction of the
    total.  Used by the dry-run's per-cell JSON and ``benchmarks/bench_zero``.
    """
    w = collective_wire_bytes(hlo_text)
    int8 = w["by_dtype"].get("s8", 0.0) + w["by_dtype"].get("u8", 0.0)
    fp32 = w["by_dtype"].get("f32", 0.0)
    total = w["total"]
    return {"total": total, "int8": int8, "fp32": fp32,
            "int8_fraction": (int8 / total) if total else 0.0}
