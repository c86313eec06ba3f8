"""Backward-overlapped bucketed wire: per-bucket compressed all-reduces.

The monolithic tree collective (:func:`~repro.dist.collectives.
dps_allreduce_mean_tree`) encodes every gradient leaf into ONE int8
buffer and ships it through one ``all_to_all``/``all_gather`` pair — so
the whole backward must finish before a single wire byte moves, and the
encode → collective → decode chain sits on the critical path end to end.

This module splits the gradient tree into DDP-style **buckets** and runs
one compressed collective pair per bucket, in the order the backward
materializes gradients (last layer first).  Each bucket's collective
depends only on that bucket's leaves, so:

* on backends with asynchronous collective dispatch, bucket k's wire
  legs overlap bucket k+1's backward compute and decode — the classic
  DDP overlap schedule (the per-bucket dependency chains are
  independent; XLA's latency-hiding scheduler is free to interleave
  them);
* on any backend, each bucket's encode/reduce/decode runs over a small
  working set instead of the whole flattened tree (cache locality), and
  per-bucket :class:`~repro.dist.collectives.GroupLayout`\\ s resolve a
  size-aware quantum per bucket, so grouped-layout padding shrinks from
  "every leaf padded against the global layout" to "every leaf padded
  against its bucket";
* the int8 wire buffers are per-bucket jit temporaries: XLA double
  buffers them (bucket k's buffer is dead — and its allocation reusable
  — by the time bucket k+2 encodes), instead of holding one tree-sized
  wire buffer live across the whole sync.

Determinism and bit-exactness contract (pinned by tests/test_overlap.py):

* ``BucketPlan`` is static Python — buckets are contiguous runs of leaf
  indices, emitted in REVERSE flatten order (the backward's
  materialization order), every leaf exactly once.
* Leg-1 rounding keys are derived from the GLOBAL leaf index
  (``fold_in(k1, g)``), exactly like the monolithic tree collective, so
  dispatch-leg wire bytes and the returned per-leaf stats are
  bit-identical to the monolithic path under both rounding modes.
* Gather-leg rounding bits are ALSO keyed by global leaf index
  (:func:`~repro.dist.collectives._leg2_bits` with the bucket's first
  leaf as ``group_offset``), so the decoded bucketed mean is
  **bit-exact** vs the monolithic collective under BOTH rounding
  modes: encode/decode are elementwise deterministic, the receive-leg
  sums run in identical rank order, and every rounding-bit draw is a
  function of (leaf, element offset) alone — chunk and bucket geometry
  cannot change a single ulp (pinned by
  tests/test_overlap.py::test_bucketed_bitexact_both_modes).

Every bucket is wrapped in ``wire_bucket`` trace-time tags (see
:mod:`repro.core.tagging`): ``stage="ready"`` on each raw leaf the
moment the bucket is handed to the wire, ``stage="mean"`` on the decoded
mean.  The precision-flow verifier's PF-BUCKET rules
(:mod:`repro.analysis.flow`) prove from the jaxpr that every ready
bucket is encoded exactly once and decoded before the optimizer consumes
it.  ``bucket_ready_tap`` additionally plants a ``stage="grad"``
landmark inside the *backward* itself (a custom-vjp identity on the
parameters), marking where each bucket's gradients materialize — the
readiness point the overlap schedule keys on.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tagging
from repro.core.fixed_point import (FixedPointFormat, QuantStats,
                                    ROUND_STOCHASTIC)
from repro.dist.collectives import (_aligned_allreduce_mean,
                                    _aligned_rs_snap, _decode_aligned,
                                    _encode_aligned, _group_layout,
                                    _leg2_bits, _pad_reshape,
                                    _resolve_backend, _resolve_quantum,
                                    _validate_capacity, _wire_reduce,
                                    group_layout, resolve_domain_format,
                                    wire_all_to_all, wire_decode,
                                    wire_encode)

# Default bucket granularity, in elements.  Small enough that a LeNet-
# scale tree still splits into a few buckets (so the schedule is
# exercised at test scale), large enough that per-bucket collective
# launch overhead stays negligible for multi-MiB layers — DDP's 25 MB
# fp32 default is ~6.5M elements; revisit when a single transformer
# block exceeds this by orders of magnitude.
DEFAULT_BUCKET_ELEMS = 1 << 16


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Static assignment of gradient-tree leaves to wire buckets.

    ``buckets[b]`` is the ascending, contiguous run of global leaf
    indices (flatten order) that bucket ``b`` syncs; buckets are listed
    in **ready order** — reverse flatten order, because the backward
    materializes the last layer's gradients first.  All fields are
    Python ints: the plan is part of the jit closure, never traced.
    """

    sizes: Tuple[int, ...]              # per-leaf element counts
    buckets: Tuple[Tuple[int, ...], ...]  # ready-order leaf-index runs
    target: int                         # requested elements per bucket

    def __post_init__(self):
        n = len(self.sizes)
        if not self.buckets and n:
            raise ValueError("empty bucket list for a non-empty tree")
        flat = [g for b in self.buckets for g in b]
        if sorted(flat) != list(range(n)):
            raise ValueError(
                f"buckets {self.buckets} are not a partition of the "
                f"{n} leaves: every leaf must appear exactly once")
        stop = n
        for b, run in enumerate(self.buckets):
            if not run:
                raise ValueError(f"bucket {b} is empty")
            if list(run) != list(range(run[0], run[0] + len(run))):
                raise ValueError(
                    f"bucket {b} = {run} is not a contiguous ascending "
                    "run of leaf indices")
            if run[-1] != stop - 1:
                raise ValueError(
                    f"buckets must cover leaves in reverse flatten order "
                    f"(the backward's ready order): bucket {b} ends at "
                    f"leaf {run[-1]}, expected {stop - 1}")
            stop = run[0]

    @property
    def n_buckets(self) -> int:
        return len(self.buckets)

    @property
    def n_leaves(self) -> int:
        return len(self.sizes)

    def bucket_of(self, leaf: int) -> int:
        """The bucket index owning global leaf ``leaf``."""
        for b, run in enumerate(self.buckets):
            if run[0] <= leaf <= run[-1]:
                return b
        raise IndexError(f"leaf {leaf} not in any bucket")

    def bucket_elems(self, b: int) -> int:
        return sum(self.sizes[g] for g in self.buckets[b])


def plan_buckets(sizes, target_elems: int = DEFAULT_BUCKET_ELEMS
                 ) -> BucketPlan:
    """Greedy reverse-order bucketing: walk leaves from the LAST flatten
    index down (the order the backward produces gradients), open a new
    bucket whenever the current one already holds ``target_elems``
    elements.  Every bucket gets at least one leaf, so a single leaf
    larger than the target becomes its own bucket rather than stalling
    the schedule."""
    sizes = tuple(int(s) for s in sizes)
    if target_elems < 1:
        raise ValueError(f"target_elems must be >= 1, got {target_elems}")
    buckets, run, acc = [], [], 0
    for g in range(len(sizes) - 1, -1, -1):
        if run and acc + sizes[g] > target_elems:
            buckets.append(tuple(reversed(run)))
            run, acc = [], 0
        run.append(g)
        acc += sizes[g]
    if run:
        buckets.append(tuple(reversed(run)))
    return BucketPlan(sizes=sizes, buckets=tuple(buckets),
                      target=int(target_elems))


# -------------------------------------------------- gradient-readiness taps

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def bucket_ready_tap(x, bucket: int, leaf: int, n_buckets: int):
    """Identity on the forward; on the backward, tags the cotangent —
    the leaf's gradient, at the exact point the backward materializes
    it — with a ``wire_bucket`` ``stage="grad"`` landmark.  The tag is
    the :data:`~repro.core.tagging.dps_tag` identity primitive: it
    lowers to nothing, so the tap is free at runtime; it exists so the
    readiness order is *visible in the jaxpr* (the per-bucket collective
    chains hang off these points) and checkable by the flow verifier."""
    return x


def _tap_fwd(x, bucket, leaf, n_buckets):
    return x, None


def _tap_bwd(bucket, leaf, n_buckets, _, cot):
    return (tagging.tag(cot, "wire_bucket", stage="grad", bucket=bucket,
                        leaf=leaf, n=n_buckets),)


bucket_ready_tap.defvjp(_tap_fwd, _tap_bwd)


def tap_params(params, plan: BucketPlan):
    """Wrap every param leaf in its bucket's readiness tap (identity
    forward; gradient-materialization landmark backward).  Apply to the
    parameters entering the loss so each grad leaf is born tagged."""
    leaves, treedef = jax.tree_util.tree_flatten(params)
    if len(leaves) != plan.n_leaves:
        raise ValueError(
            f"param tree has {len(leaves)} leaves but the bucket plan "
            f"covers {plan.n_leaves}")
    out = [bucket_ready_tap(l, plan.bucket_of(g), g, plan.n_buckets)
           for g, l in enumerate(leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)


# ------------------------------------------------- the bucketed collective

def bucketed_allreduce_mean_tree(tree, formats, axis_name, key,
                                 *, mode: str = ROUND_STOCHASTIC,
                                 backend: str = "auto",
                                 domain: str = "wire_grads",
                                 quantum: Optional[int] = None,
                                 plan: Optional[BucketPlan] = None,
                                 target_elems: int = DEFAULT_BUCKET_ELEMS):
    """Bucketed :func:`~repro.dist.collectives.dps_allreduce_mean_tree`:
    one compressed ``all_to_all``/``all_gather`` pair **per bucket**, in
    backward ready order, instead of one monolithic pair for the tree.

    Same contract as the monolithic collective — ``(mean_tree, stats)``,
    every leaf cast back to its own dtype, stats ``[G]``-stacked in leaf
    order for grouped formats or merged in leaf order for a scalar
    format, dispatch-leg stats covering exactly this rank's |tree|
    elements — and bit-identical wire bytes / stats on the dispatch leg
    (leg-1 rounding keys are global-leaf-indexed in both).  The decoded
    mean is bit-exact vs the monolithic path under BOTH rounding modes:
    gather-leg bits are global-leaf-indexed too (see the module
    docstring).

    ``plan=None`` derives :func:`plan_buckets` over the leaf sizes with
    ``target_elems``; a caller-supplied plan must match the tree's leaf
    sizes (the qtrain readiness taps and this collective must agree on
    the bucket → leaf mapping).
    """
    fmt = resolve_domain_format(formats, domain)
    _validate_capacity(fmt)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return tree, QuantStats.zero(fmt.il.shape)
    grouped = fmt.il.ndim != 0
    if grouped and fmt.il.shape[0] != len(leaves):
        raise ValueError(
            f"[G]-shaped tree formats are one ⟨IL, FL⟩ per leaf: the table "
            f"has {fmt.il.shape[0]} rows, the tree {len(leaves)} leaves")
    sizes = tuple(l.size for l in leaves)
    if plan is None:
        plan = plan_buckets(sizes, target_elems)
    elif plan.sizes != sizes:
        raise ValueError(
            f"bucket plan was built for leaf sizes {plan.sizes} but the "
            f"tree has {sizes}; scheduler and collective must share one "
            "plan")
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    k1, k2 = jax.random.split(jax.random.fold_in(key, idx))
    del k2  # gather-leg bits come from the rank-invariant k2s stream
    # leg-2 bits are element-indexed and keyed by GLOBAL leaf index
    # (collectives._leg2_bits): the same rank-invariant fold as the
    # monolithic path, with each bucket passing its first leaf's global
    # index as group_offset — so every leaf draws the exact bits the
    # monolithic layout would, and bucketing is invisible under
    # stochastic rounding.
    k2s = jax.random.fold_in(key, 0x4C454732)                # "LEG2"
    be = _resolve_backend(backend)
    B = plan.n_buckets

    out = [None] * len(leaves)
    leaf_stats = [None] * len(leaves)

    with tagging.domain(domain):
        for b, run in enumerate(plan.buckets):
            bleaves = [
                tagging.tag(leaves[g], "wire_bucket", stage="ready",
                            bucket=b, leaf=g, n=B)
                for g in run]
            bsizes = tuple(sizes[g] for g in run)
            if grouped:
                lo, hi = run[0], run[-1] + 1
                fmt_b = FixedPointFormat(fmt.il[lo:hi], fmt.fl[lo:hi])
                q = _resolve_quantum(quantum, sum(bsizes), len(run), be)
                layout = group_layout(bsizes, n_chunks=n, quantum=q)

                def encode_leg1(tg_all, mask, _run=run, _bl=bleaves,
                                _fmt=fmt_b, _lay=layout):
                    buf = jnp.zeros((_lay.total,), jnp.int8)
                    for j, g in enumerate(_run):
                        fmt_g = FixedPointFormat(_fmt.il[j], _fmt.fl[j])
                        w, s = wire_encode(
                            _bl[j].reshape(-1), fmt_g,
                            key=jax.random.fold_in(k1, g), mode=mode,
                            backend=be)
                        buf = jax.lax.dynamic_update_slice(
                            buf, w, (_lay.offsets[j],))
                        leaf_stats[g] = s
                    per = [leaf_stats[g] for g in _run]
                    return buf, jax.tree.map(lambda *xs: jnp.stack(xs),
                                             *per)

                mean_al, _ = _aligned_allreduce_mean(
                    None, fmt_b, layout, axis_name, k1, k2s,
                    mode=mode, backend=be, group_offset=lo,
                    encode_leg1=encode_leg1)
                mean_al = tagging.tag(mean_al, "wire_bucket", stage="mean",
                                      bucket=b, n=B)
                for j, g in enumerate(run):
                    sl = jax.lax.dynamic_slice(
                        mean_al, (layout.offsets[j],), (sizes[g],))
                    out[g] = sl.reshape(leaves[g].shape).astype(
                        leaves[g].dtype)
            else:
                size_b = sum(bsizes)
                chunk, _ = _group_layout(size_b, n)
                offsets = tuple(int(o)
                                for o in np.cumsum((0,) + bsizes[:-1]))
                total = chunk * n
                q = _resolve_quantum(quantum, size_b, 1, be)
                buf = jnp.zeros((total,), jnp.int8)
                for j, g in enumerate(run):
                    w, s = wire_encode(bleaves[j].reshape(-1), fmt,
                                       key=jax.random.fold_in(k1, g),
                                       mode=mode, backend=be)
                    buf = jax.lax.dynamic_update_slice(buf, w, (offsets[j],))
                    leaf_stats[g] = s
                payload = tagging.tag(buf.reshape(n, chunk), "wire_payload",
                                      leg="dispatch")
                wire = wire_all_to_all(payload, axis_name)
                part = _wire_reduce(wire, fmt, None, backend=be, quantum=q)
                if mode == ROUND_STOCHASTIC:
                    bits2 = jax.lax.dynamic_slice(
                        _pad_reshape(_leg2_bits(k2s, bsizes, run[0]),
                                     total - size_b, (total,)),
                        (idx * chunk,), (chunk,))
                else:
                    bits2 = None
                wire2, _ = wire_encode(part, fmt, bits=bits2,
                                       mode=mode, compute_stats=False,
                                       backend=be)
                wire2 = tagging.tag(wire2, "wire_payload", leg="gather")
                full = jax.lax.all_gather(wire2, axis_name, axis=0,
                                          tiled=True)
                for j, g in enumerate(run):
                    dec = wire_decode(
                        jax.lax.dynamic_slice(full, (offsets[j],),
                                              (sizes[g],)), fmt)
                    dec = tagging.tag(dec, "wire_bucket", stage="mean",
                                      bucket=b, n=B)
                    out[g] = dec.reshape(leaves[g].shape).astype(
                        leaves[g].dtype)

        # reassemble stats in GLOBAL leaf order — the same stack/merge
        # order as the monolithic tree collective, so the controller
        # stream is bit-identical to the un-bucketed path.
        if grouped:
            stats = jax.tree.map(lambda *xs: jnp.stack(xs), *leaf_stats)
        else:
            stats = leaf_stats[0]
            for s in leaf_stats[1:]:
                stats = stats.merge(s)
        stats = tagging.tag_tree(stats, "wire_stats")

    return jax.tree_util.tree_unflatten(treedef, out), stats


# ------------------------------------------- the sharded (ZeRO-1) halves

def _bucket_format(fmt: FixedPointFormat, lo: int, gb: int,
                   grouped: bool) -> FixedPointFormat:
    """Bucket rows ``[lo, lo + gb)`` of a per-leaf ``[G]`` format table —
    or a scalar format broadcast to ``gb`` identical rows, so the aligned
    codec (which resolves per-tile formats from a row table) runs the
    scalar grid unchanged."""
    if grouped:
        return FixedPointFormat(fmt.il[lo:lo + gb], fmt.fl[lo:lo + gb])
    return FixedPointFormat(
        jnp.broadcast_to(jnp.asarray(fmt.il), (gb,)),
        jnp.broadcast_to(jnp.asarray(fmt.fl), (gb,)))


def _check_partitioner(part, n: int, n_leaves: int, fmt: FixedPointFormat,
                       backend: str, what: str):
    be = _resolve_backend(backend)
    if be != part.backend:
        raise ValueError(
            f"{what}: partitioner layout was built for the "
            f"{part.backend!r} codec backend but the collective resolved "
            f"{be!r}; build the GroupAlignedPartitioner with the backend "
            "the step runs")
    if n != part.n_shards:
        raise ValueError(
            f"{what}: partitioner has n_shards={part.n_shards} but the "
            f"mesh axis has {n} ranks")
    if len(part.shapes) != n_leaves:
        raise ValueError(
            f"{what}: partitioner covers {len(part.shapes)} leaves, "
            f"got {n_leaves}")
    if fmt.il.ndim != 0 and fmt.il.shape[0] != n_leaves:
        raise ValueError(
            f"[G]-shaped formats are one ⟨IL, FL⟩ per leaf: the table has "
            f"{fmt.il.shape[0]} rows, the tree {n_leaves} leaves")
    return be


def zero_bucketed_reduce_scatter(tree, formats, axis_name, key, *, part,
                                 mode: str = ROUND_STOCHASTIC,
                                 backend: str = "auto",
                                 domain: str = "wire_grads",
                                 tag_buckets: bool = False):
    """Compressed gradient reduce-scatter onto a group-aligned ZeRO shard.

    The sharded first half of :func:`bucketed_allreduce_mean_tree`: one
    int8 ``all_to_all`` per bucket of ``part`` (a
    :class:`repro.dist.sharding.GroupAlignedPartitioner`), walked in
    backward-ready order (reverse flatten order), each followed by the
    fused decode-reduce of the owned chunk and a LOCAL wire-grid snap
    (:func:`~repro.dist.collectives._aligned_rs_snap`) — the re-encode +
    decode the all-reduce's gather leg would have applied, minus the
    gather.  Rank r therefore holds values bit-identical to its chunk of
    the replicated collective's decoded mean, under both rounding modes
    (every rounding-bit draw is keyed by global leaf index; see the
    module docstring), which is what makes ZeRO + per-layer wire +
    overlap bit-exact with the replicated per-layer step.

    ``formats`` may be scalar (one wire grid everywhere) or per-leaf
    ``[G]``-shaped; stats come back in the same shape, assembled in
    global leaf order exactly like the replicated collectives.

    ``tag_buckets=True`` wraps every bucket in the ``wire_bucket``
    ready/mean trace tags the PF-BUCKET verifier rules consume — turn it
    on exactly when the gradients carry :func:`bucket_ready_tap`
    landmarks (the overlapped step), whose plan must list this
    partitioner's buckets in reverse order.

    Returns ``(gshard fp32 [part.shard_size], stats)``; ``gshard`` is
    this rank's concatenated per-bucket chunks of the snapped mean —
    ``part.shard(part.flatten(mean_tree), rank)`` of the replicated
    result.  Must run inside ``shard_map``; ``key`` may be identical
    across ranks.
    """
    fmt = resolve_domain_format(formats, domain)
    _validate_capacity(fmt)
    leaves, _ = jax.tree_util.tree_flatten(tree)
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    be = _check_partitioner(part, n, len(leaves), fmt, backend,
                            "zero_bucketed_reduce_scatter")
    grouped = fmt.il.ndim != 0
    k1, _ = jax.random.split(jax.random.fold_in(key, idx))
    k2s = jax.random.fold_in(key, 0x4C454732)                # "LEG2"
    B = part.n_buckets

    chunks = [None] * B
    leaf_stats = [None] * len(leaves)
    with tagging.domain(domain):
        for rb in range(B):          # ready order = reverse flatten order
            pb = B - 1 - rb
            run = part.buckets[pb]
            lay = part.layouts[pb]
            lo, gb = run[0], len(run)
            fmt_b = _bucket_format(fmt, lo, gb, grouped)
            bleaves = [
                tagging.tag(leaves[g], "wire_bucket", stage="ready",
                            bucket=rb, leaf=g, n=B) if tag_buckets
                else leaves[g]
                for g in run]

            def encode_leg1(tg_all, mask, _run=run, _bl=bleaves,
                            _fmt=fmt_b, _lay=lay):
                buf = jnp.zeros((_lay.total,), jnp.int8)
                for j, g in enumerate(_run):
                    fmt_g = FixedPointFormat(_fmt.il[j], _fmt.fl[j])
                    w, s = wire_encode(
                        _bl[j].reshape(-1), fmt_g,
                        key=jax.random.fold_in(k1, g), mode=mode,
                        backend=be)
                    buf = jax.lax.dynamic_update_slice(
                        buf, w, (_lay.offsets[j],))
                    leaf_stats[g] = s
                per = [leaf_stats[g] for g in _run]
                return buf, jax.tree.map(lambda *xs: jnp.stack(xs), *per)

            _, wire2, _, my_tg = _aligned_rs_snap(
                None, fmt_b, lay, axis_name, k1, k2s, mode=mode,
                backend=be, group_offset=lo, encode_leg1=encode_leg1)
            dec = _decode_aligned(wire2, fmt_b, my_tg, lay.quantum)
            if tag_buckets:
                dec = tagging.tag(dec, "wire_bucket", stage="mean",
                                  bucket=rb, n=B)
            chunks[pb] = dec

        # stats in GLOBAL leaf order, same as the replicated collectives
        if grouped:
            stats = jax.tree.map(lambda *xs: jnp.stack(xs), *leaf_stats)
        else:
            stats = leaf_stats[0]
            for s in leaf_stats[1:]:
                stats = stats.merge(s)
        stats = tagging.tag_tree(stats, "wire_stats")

    gshard = chunks[0] if B == 1 else jnp.concatenate(chunks)
    return gshard, stats


def zero_allgather_params(shard: jax.Array, formats, axis_name, key, *,
                          part, mode: str = ROUND_STOCHASTIC,
                          backend: str = "auto",
                          domain: str = "wire_params"):
    """Compressed parameter all-gather from group-aligned ZeRO shards.

    The sharded return leg: each rank encodes its ``[part.shard_size]``
    slice of the updated flat parameter vector bucket-segment by
    bucket-segment with the aligned codec (per-tile formats from the
    bucket's row table, alignment padding masked out of the stats),
    ships ONE concatenated int8 ``all_gather``, and decodes the full
    group-aligned buffer.  ``formats`` may be scalar or per-leaf
    ``[G]``-shaped (``wire_params`` rows in leaf order).

    Returns ``(flat fp32 [part.padded_size], stats)``: ``flat`` is the
    decoded aligned parameter buffer (``part.unflatten`` restores the
    tree), ``stats`` cover this rank's encode of its shard elements
    (``psum_stats`` counts each global element exactly once).  Must run
    inside ``shard_map``; ``key`` may be identical across ranks.
    """
    fmt = resolve_domain_format(formats, domain)
    _validate_capacity(fmt)
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    be = _check_partitioner(part, n, len(part.shapes), fmt, backend,
                            "zero_allgather_params")
    grouped = fmt.il.ndim != 0
    # gather-leg-style element-indexed bits (rank-invariant stream keyed
    # by global leaf index): rank r's draws depend only on which elements
    # it owns, not on r itself
    kps = jax.random.fold_in(key, 0x57504C47)                # "WPLG"

    wire_chunks, stat_rows = [], []
    with tagging.domain(domain):
        for pb in range(part.n_buckets):
            run = part.buckets[pb]
            lay = part.layouts[pb]
            lo, gb = run[0], len(run)
            fmt_b = _bucket_format(fmt, lo, gb, grouped)
            tg_all = jnp.asarray(lay.tile_groups())
            tpc = lay.chunk // lay.quantum
            my_tg = jax.lax.dynamic_slice(tg_all, (idx * tpc,), (tpc,))
            my_mask = jax.lax.dynamic_slice(
                lay.mask(), (idx * lay.chunk,), (lay.chunk,))
            soff = part.shard_offset(pb)
            seg = jax.lax.slice(shard, (soff,), (soff + lay.chunk,))
            if mode == ROUND_STOCHASTIC:
                bits = jax.lax.dynamic_slice(
                    lay.align(_leg2_bits(kps, lay.group_sizes, lo)),
                    (idx * lay.chunk,), (lay.chunk,))
            else:
                bits = None
            w, s = _encode_aligned(seg, fmt_b, my_tg, my_mask, bits=bits,
                                   mode=mode, backend=be,
                                   quantum=lay.quantum)
            wire_chunks.append(w)
            stat_rows.append(s)

        wire = (wire_chunks[0] if len(wire_chunks) == 1
                else jnp.concatenate(wire_chunks))
        wire = tagging.tag(wire, "wire_payload", leg="gather")
        gathered = jax.lax.all_gather(wire, axis_name, axis=0, tiled=True)
        gathered = gathered.reshape(n, part.shard_size)

        segs = []
        for pb in range(part.n_buckets):
            run = part.buckets[pb]
            lay = part.layouts[pb]
            lo, gb = run[0], len(run)
            soff = part.shard_offset(pb)
            seg_full = gathered[:, soff:soff + lay.chunk].reshape(
                n * lay.chunk)
            segs.append(_decode_aligned(
                seg_full, _bucket_format(fmt, lo, gb, grouped),
                jnp.asarray(lay.tile_groups()), lay.quantum))
        flat = segs[0] if len(segs) == 1 else jnp.concatenate(segs)

        rows = (stat_rows[0] if len(stat_rows) == 1
                else jax.tree.map(lambda *xs: jnp.concatenate(xs),
                                  *stat_rows))
        if grouped:
            stats = rows
        else:
            # scalar wire_params domain: collapse the per-leaf rows
            stats = QuantStats(
                count=rows.count.sum(), nonzero=rows.nonzero.sum(),
                overflow=rows.overflow.sum(),
                abs_err_sum=rows.abs_err_sum.sum(),
                rel_err_sum=rows.rel_err_sum.sum(),
                abs_sum=rows.abs_sum.sum(), max_abs=rows.max_abs.max())
        stats = tagging.tag_tree(stats, "wire_stats")
    return flat, stats
