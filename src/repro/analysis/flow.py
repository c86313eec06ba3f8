"""Jaxpr dataflow pass: taint-propagation over declared wire tag sites.

The wire pipeline declares its own landmarks at trace time via the
``dps_tag`` identity primitive (:mod:`repro.core.tagging`): encode
entries, decode exits, collective payloads, stats streams, SR bits.  This
pass walks the ClosedJaxpr of any step — train, ZeRO, tree, serve — and
propagates taint labels from those landmarks to prove four invariants:

``PF-WIRE-F32``
    A wire-payload value must reach its collective as int8.  Fires when a
    ``wire_payload``-tainted operand of a collective primitive has a
    non-int8 dtype, and when any ``all_to_all`` carries non-int8 data in
    a step that uses the wire machinery at all (the all-to-all exists in
    this codebase only as the compressed dispatch leg, so fp32 there
    means an encode was skipped).

``PF-REQUANT``
    A decode output feeding an encode input with no intervening compute
    is a pure dequant→requant round-trip: wire bytes and rounding noise
    spent to reproduce (at best) the same payload.  ``decode_out`` taint
    survives only *structural* ops (reshape/slice/transpose/...); any
    arithmetic kills it.

``PF-STATS-ROUTE``
    Wire-leg statistics must steer wire controllers.  Fires when
    ``wire_stats`` taint reaches a ``stats_sink`` tag whose domain is
    declared ``wire=False`` — the PR-4 bug class where compressed-grad
    stats starved the compute-grads controller.

``PF-SR-SEED``
    A stochastic encode's ``sr_bits`` operand must descend from a PRNG
    (threefry/random primitives).  Fires when the bits are constants or
    otherwise PRNG-free — silently deterministic "stochastic" rounding.

``PF-BUCKET-ENCODE`` / ``PF-BUCKET-DECODE``
    The bucketed-wire invariants (:mod:`repro.dist.overlap`).  Every
    leaf the scheduler tags ``wire_bucket stage="ready"`` must reach a
    wire encode at **exactly one** site — zero sites is a dropped leaf
    (its gradient never syncs), two is a double-encoded payload (wire
    bytes and rounding noise spent twice, and under stochastic rounding
    the copies disagree) — and the declared bucket count ``n`` must be
    fully covered by ready tags.  Every ``stage="mean"`` tag must carry
    ``decode_out`` taint (the optimizer consumes a *decoded* bucket, not
    raw wire bytes) and every ready bucket must have one.  Encode sites
    are identified by jaxpr path, so fixpoint re-walks of ``while``
    bodies do not double-count.  Both rules are vacuous (still marked
    checked only when bucket tags exist) on un-bucketed steps.

``PF-GUARD-TAINT``
    The resilience invariant (:mod:`repro.resilience`).  A health-guard
    degradation signal (tagged ``guard_sink``) must descend from
    ``wire_stats`` taint in any wire-enabled step: a guard fed from
    post-fallback values (zero stats fabricated after the fp32 branch)
    or from constants would latch permanently or never trip.  Vacuous in
    steps that never put payload on the wire (the tag sites only mark
    engaged legs).

``PF-KV-WIRE``
    The serving-side invariant (:mod:`repro.serve`).  A paged-KV step
    tags the page-pool writes and reads ``kv_page`` with the configured
    wire width; at ``bits=8`` the tagged value must be int8 grid
    integers — an fp32 page write/read means the decode step silently
    fell back to an uncompressed cache while claiming int8 paging.

Taint crosses ``pjit`` / ``shard_map`` / ``scan`` / ``while`` / ``cond``
/ custom-derivative sub-jaxprs.  ``wire_stats`` and ``prng`` survive all
ops (stats get stacked and reduced; keys get folded); ``wire_payload``,
``decode_out`` and the per-leaf ``bucket_ready:<b>:<g>`` labels survive
only structural ops.
"""

from __future__ import annotations

from typing import Dict, Iterable, Optional, Set, Tuple

import jax
from jax.extend import core as jax_core

from repro.analysis.report import Report
from repro.core import tagging

# primitives that move bytes across ranks
COLLECTIVE_PRIMS = frozenset({
    "psum", "pmax", "pmin", "all_to_all", "all_gather", "ppermute",
    "psum_scatter", "reduce_scatter", "pgather", "all_gather_invariant",
})

# shape/layout-only ops: values pass through unchanged (taint survives)
STRUCTURAL_PRIMS = frozenset({
    "reshape", "transpose", "broadcast_in_dim", "squeeze", "slice",
    "dynamic_slice", "dynamic_update_slice", "concatenate",
    "convert_element_type", "copy", "pad", "rev", "gather", "expand_dims",
    "select_n", "bitcast_convert_type",
})

# taints that die at the first non-structural op
_STRUCTURAL_ONLY = frozenset({"wire_payload", "decode_out"})

# structural-only taint family for bucketed-wire readiness: one label per
# (bucket, leaf), "bucket_ready:<b>:<g>"
_BUCKET_READY = "bucket_ready:"

_INT8 = ("int8", "uint8")


def _is_prng_prim(name: str) -> bool:
    return ("threefry" in name or "prng" in name or name.startswith("random_")
            or name == "rng_bit_generator")


def _aval_dtype(v) -> Optional[str]:
    aval = getattr(v, "aval", None)
    dtype = getattr(aval, "dtype", None)
    return None if dtype is None else str(dtype)


class _Walker:
    """One taint walk over a jaxpr and all of its sub-jaxprs."""

    def __init__(self, report: Report):
        self.report = report
        self.taints: Dict[jax_core.Var, Set[str]] = {}
        self.uses_wire = False          # any wire_payload tag seen anywhere
        # bucketed-wire bookkeeping (repro.dist.overlap): ready-tagged
        # (bucket, leaf) -> set of encode-site jaxpr paths; bucket ->
        # list of (where, descends-from-decode) mean tags; declared
        # bucket count; stage="grad" readiness-tap bucket ids.
        self.bucket_sites: Dict[Tuple[int, int], Set[str]] = {}
        self.bucket_means: Dict[int, list] = {}
        self.bucket_n: int = 0
        self.grad_buckets: Set[int] = set()

    # -- taint bookkeeping -------------------------------------------------

    def t(self, v) -> Set[str]:
        if isinstance(v, jax_core.Literal):
            return set()
        return self.taints.get(v, set())

    def set_t(self, v, labels: Set[str]) -> bool:
        """Union ``labels`` into v's taints; True when anything was new."""
        if isinstance(v, jax_core.Literal) or not labels:
            return False
        cur = self.taints.setdefault(v, set())
        before = len(cur)
        cur |= labels
        return len(cur) != before

    # -- the walk ----------------------------------------------------------

    def walk(self, jaxpr: jax_core.Jaxpr, path: str = "") -> None:
        for i, eqn in enumerate(jaxpr.eqns):
            self.eqn(eqn, f"{path}eqn{i}:{eqn.primitive.name}")

    def eqn(self, eqn, where: str) -> None:
        name = eqn.primitive.name
        in_taints: Set[str] = set()
        for v in eqn.invars:
            in_taints |= self.t(v)

        if name == tagging.TAG_PRIMITIVE_NAME:
            self.tag_eqn(eqn, in_taints, where)
            return

        if self.descend(eqn, where):
            return

        if name in COLLECTIVE_PRIMS:
            self.collective_eqn(eqn, where)

        if _is_prng_prim(name):
            in_taints = in_taints | {"prng"}
        if name not in STRUCTURAL_PRIMS:
            in_taints = {t for t in in_taints
                         if t not in _STRUCTURAL_ONLY
                         and not t.startswith(_BUCKET_READY)}
        for o in eqn.outvars:
            self.set_t(o, in_taints)

    def tag_eqn(self, eqn, in_taints: Set[str], where: str) -> None:
        params = tagging.tag_params(eqn.params) or {}
        kind = params.get("kind", "?")
        dom = params.get("domain")
        out_taints = set(in_taints)

        if kind == "encode_in":
            self.report.mark_checked("PF-REQUANT")
            for t in in_taints:
                if t.startswith(_BUCKET_READY):
                    b, g = (int(p) for p in t[len(_BUCKET_READY):].split(":"))
                    self.bucket_sites.setdefault((b, g), set()).add(where)
            if "decode_out" in in_taints:
                self.report.add(
                    "PF-REQUANT",
                    f"decode output re-enters an encode with no intervening "
                    f"compute (domain {dom!r}): a pure dequant→requant "
                    f"round-trip burning wire bytes and rounding noise",
                    where)
        elif kind == "decode_out":
            out_taints.add("decode_out")
        elif kind == "wire_payload":
            self.uses_wire = True
            out_taints.add("wire_payload")
        elif kind == "wire_stats":
            out_taints.add("wire_stats")
        elif kind == "sr_bits":
            self.report.mark_checked("PF-SR-SEED")
            if "prng" not in in_taints:
                self.report.add(
                    "PF-SR-SEED",
                    f"stochastic-rounding bits (domain {dom!r}) do not "
                    f"descend from any PRNG primitive — the 'stochastic' "
                    f"path is silently deterministic",
                    where)
        elif kind == "wire_bucket":
            stage = params.get("stage")
            b = int(params.get("bucket", -1))
            self.bucket_n = max(self.bucket_n, int(params.get("n", 0)))
            if stage == "ready":
                g = int(params.get("leaf", -1))
                self.bucket_sites.setdefault((b, g), set())
                out_taints.add(f"{_BUCKET_READY}{b}:{g}")
            elif stage == "mean":
                self.bucket_means.setdefault(b, []).append(
                    (where, "decode_out" in in_taints))
            elif stage == "grad":
                self.grad_buckets.add(b)
        elif kind == "kv_page":
            self.report.mark_checked("PF-KV-WIRE")
            bits = int(params.get("bits", 0) or 0)
            dtype = _aval_dtype(eqn.invars[0])
            if bits == 8 and dtype is not None and dtype not in _INT8:
                self.report.add(
                    "PF-KV-WIRE",
                    f"paged KV cache {params.get('stage', '?')} (domain "
                    f"{dom!r}) claims {bits}-bit pages but carries {dtype} "
                    f"— the page pool contract is int8 grid integers",
                    where)
        elif kind == "guard_sink":
            self.report.mark_checked("PF-GUARD-TAINT")
            if self.uses_wire and "wire_stats" not in in_taints:
                self.report.add(
                    "PF-GUARD-TAINT",
                    f"the health-guard signal for domain {dom!r} does not "
                    f"descend from wire-leg statistics — a degradation "
                    f"decision fed by post-fallback (or fabricated) values "
                    f"can never see the storm it exists to detect",
                    where)
        elif kind == "stats_sink":
            self.report.mark_checked("PF-STATS-ROUTE")
            if not params.get("wire", False) and "wire_stats" in in_taints:
                self.report.add(
                    "PF-STATS-ROUTE",
                    f"wire-leg statistics reach the non-wire controller of "
                    f"domain {dom!r} (stream {params.get('stream')!r}) — "
                    f"compressed-wire error/overflow would steer a compute "
                    f"format",
                    where)
        for o in eqn.outvars:
            self.set_t(o, out_taints)

    def collective_eqn(self, eqn, where: str) -> None:
        self.report.mark_checked("PF-WIRE-F32")
        name = eqn.primitive.name
        for v in eqn.invars:
            dtype = _aval_dtype(v)
            if dtype is None or dtype in _INT8:
                continue
            tainted = "wire_payload" in self.t(v)
            if tainted or (name == "all_to_all" and self.uses_wire):
                why = ("a wire-payload value" if tainted else
                       "an all-to-all operand in a wire-enabled step")
                self.report.add(
                    "PF-WIRE-F32",
                    f"{why} reaches collective {name!r} as {dtype} — the "
                    f"wire contract is int8 grid integers only",
                    where)

    def finalize_buckets(self) -> None:
        """Post-walk bucket accounting: PF-BUCKET-ENCODE (every ready
        leaf encoded at exactly one site, declared bucket count covered)
        and PF-BUCKET-DECODE (every ready bucket has a mean tag that
        descends from a wire decode).  Vacuous when the step carries no
        ``wire_bucket`` tags."""
        if not (self.bucket_sites or self.bucket_means or self.grad_buckets):
            return
        self.report.mark_checked("PF-BUCKET-ENCODE", "PF-BUCKET-DECODE")
        ready = {b for b, _ in self.bucket_sites}
        for (b, g), sites in sorted(self.bucket_sites.items()):
            if not sites:
                self.report.add(
                    "PF-BUCKET-ENCODE",
                    f"bucket {b} leaf {g} is tagged ready but never "
                    f"reaches a wire encode — the leaf's gradient would "
                    f"be dropped from the synced mean",
                    "<bucket-finalize>")
            elif len(sites) > 1:
                self.report.add(
                    "PF-BUCKET-ENCODE",
                    f"bucket {b} leaf {g} reaches {len(sites)} distinct "
                    f"wire encodes — a double-encoded payload (wire bytes "
                    f"spent twice; stochastic copies disagree)",
                    sorted(sites)[0])
        if self.bucket_n and ready and ready != set(range(self.bucket_n)):
            missing = sorted(set(range(self.bucket_n)) - ready)
            self.report.add(
                "PF-BUCKET-ENCODE",
                f"the schedule declares {self.bucket_n} buckets but ready "
                f"tags cover only {sorted(ready)} (missing {missing})",
                "<bucket-finalize>")
        if self.grad_buckets and ready and self.grad_buckets != ready:
            self.report.add(
                "PF-BUCKET-ENCODE",
                f"gradient-readiness taps mark buckets "
                f"{sorted(self.grad_buckets)} but the wire consumes "
                f"{sorted(ready)} — scheduler and collective disagree on "
                f"the plan",
                "<bucket-finalize>")
        for b in sorted(ready):
            if b not in self.bucket_means:
                self.report.add(
                    "PF-BUCKET-DECODE",
                    f"bucket {b} has no decoded-mean tag — the optimizer "
                    f"would consume an unsynced (or undecoded) bucket",
                    "<bucket-finalize>")
        for b, entries in sorted(self.bucket_means.items()):
            if not any(ok for _, ok in entries):
                self.report.add(
                    "PF-BUCKET-DECODE",
                    f"bucket {b}'s mean tag does not descend from a wire "
                    f"decode — raw or re-encoded wire bytes would reach "
                    f"the optimizer",
                    entries[0][0])

    # -- sub-jaxpr descent -------------------------------------------------

    def descend(self, eqn, where: str) -> bool:
        """Propagate taint through an eqn's sub-jaxprs.  True when the eqn
        was fully handled here."""
        name = eqn.primitive.name
        params = eqn.params

        if name == "while":
            cn = params.get("cond_nconsts", 0)
            bn = params.get("body_nconsts", 0)
            body = _as_jaxpr(params["body_jaxpr"])
            cond = _as_jaxpr(params["cond_jaxpr"])
            carry = eqn.invars[cn + bn:]
            body_in = list(eqn.invars[cn:cn + bn]) + list(carry)
            # loop-carried taint: iterate the body to a fixpoint
            for _ in range(len(carry) + 2):
                changed = self.run_sub(body, body_in, eqn.outvars,
                                       f"{where}/body/")
                for o, c in zip(eqn.outvars, carry):
                    self.set_t(o, self.t(c))
                body_in = list(eqn.invars[cn:cn + bn]) + list(eqn.outvars)
                if not changed:
                    break
            self.run_sub(cond, list(eqn.invars[:cn]) + list(body_in[bn:]),
                         [], f"{where}/cond/")
            return True

        if name == "cond":
            for b, branch in enumerate(params.get("branches", ())):
                self.run_sub(_as_jaxpr(branch), eqn.invars[1:], eqn.outvars,
                             f"{where}/branch{b}/")
            return True

        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            sub = params.get(key)
            if sub is None:
                continue
            sub = _as_jaxpr(sub)
            if not isinstance(sub, jax_core.Jaxpr):
                continue
            if len(sub.invars) == len(eqn.invars):
                self.run_sub(sub, eqn.invars, eqn.outvars, f"{where}/")
            else:
                # unknown operand convention: smear every input taint over
                # every invar (conservative, never misses a flow)
                smear: Set[str] = set()
                for v in eqn.invars:
                    smear |= self.t(v)
                for iv in sub.invars:
                    self.set_t(iv, smear)
                self.walk(sub, f"{where}/")
                out: Set[str] = set()
                for ov in sub.outvars:
                    out |= self.t(ov)
                for o in eqn.outvars:
                    self.set_t(o, out)
            return True
        return False

    def run_sub(self, sub: jax_core.Jaxpr, invals, outvals,
                path: str) -> bool:
        """Positionally map taint across a sub-jaxpr boundary; True when
        any outer outval gained taint."""
        for iv, v in zip(sub.invars, invals):
            self.set_t(iv, self.t(v))
        self.walk(sub, path)
        changed = False
        for o, ov in zip(outvals, sub.outvars):
            changed |= self.set_t(o, self.t(ov))
        return changed


def _as_jaxpr(j) -> jax_core.Jaxpr:
    return j.jaxpr if isinstance(j, jax_core.ClosedJaxpr) else j


def analyze_jaxpr(jaxpr, name: str = "step") -> Report:
    """Run the dataflow pass over a (Closed)Jaxpr; returns a Report."""
    report = Report(name=name)
    report.mark_checked("PF-WIRE-F32", "PF-REQUANT",
                        "PF-STATS-ROUTE", "PF-SR-SEED")
    walker = _Walker(report)
    # two passes: the first discovers whether the step uses the wire
    # machinery at all (the all-to-all purity clause of PF-WIRE-F32 only
    # applies then); the second applies it from the start of the jaxpr.
    walker.walk(_as_jaxpr(jaxpr))
    if walker.uses_wire:
        second = _Walker(Report(name=name))
        second.uses_wire = True
        second.walk(_as_jaxpr(jaxpr))
        second.finalize_buckets()
        report.violations = second.report.violations
        report.mark_checked(*second.report.checked)
    else:
        walker.finalize_buckets()
    return report


def analyze_fn(fn, *args, name: str = "step",
               axis_env: Optional[Iterable[Tuple[str, int]]] = None,
               **kwargs) -> Report:
    """Trace ``fn(*args, **kwargs)`` to a jaxpr and analyze it.

    ``axis_env`` (e.g. ``[("data", 8)]``) lets collectives trace outside
    ``shard_map`` — used by the oracle tests; real steps trace as-is.
    """
    mk = jax.make_jaxpr(fn)
    if axis_env is not None:
        mk = jax.make_jaxpr(fn, axis_env=list(axis_env))
    return analyze_jaxpr(mk(*args, **kwargs), name=name)
