"""Compressed collectives: the paper's quantizer on the gradient wire.

A fixed-point format ⟨IL, FL⟩ with IL + FL ≤ 8 puts every grid integer in
[-128, 127], so a quantized payload travels the interconnect as **int8**
instead of fp32 — 4× fewer bytes on the wire for the two collective legs
of an all-reduce.  Stochastic rounding (Gupta et al., 2015) keeps both
legs unbiased, and the same :class:`QuantStats` the DPS controllers
consume fall out of the encode for free, so a training loop can feed each
leg's wire-quantization error straight into that leg's dedicated *wire
precision domain* (``wire_grads`` / ``wire_params`` in the
:class:`~repro.core.dps.PrecisionPlan` registry; see
``QuantConfig.grad_allreduce_bits`` in :mod:`repro.core.qtrain`).  Every
collective below takes the whole registry-format mapping and resolves its
own leg's ⟨IL, FL⟩ (:func:`resolve_domain_format`).

Codec backends: on TPU the encode runs as the fused Pallas
``dps_quant_wire`` kernel (one read-x/write-wire HBM pass, stats ride in
SMEM); elsewhere it runs as plain jnp ops.  ``backend="auto"`` picks per
:func:`repro.device.on_tpu`; both backends are bit-exact against
``repro.kernels.ref.dps_quant_wire_ref``.

Formats may be **per-group**: an ⟨IL, FL⟩ of shape ``[G]`` splits the
flattened tensor into G contiguous chunks — equal ``ceil(size / G)``
chunks by default, or explicit per-layer ``group_sizes`` (the grads DPS
controller's per-leaf state is the natural producer) — and returns
``[G]``-shaped :class:`QuantStats`.  A scalar format (the default) is the
global case.  The collectives run ``[G]`` formats through BOTH legs at
kernel speed via the **group-aligned layout** (:class:`GroupLayout`):
every group zero-padded to a multiple of the kernel's tile ``quantum``,
the whole buffer padded to rank-divisible tile-aligned chunks, so one
fused kernel launch encodes all G formats (``[G, 2]`` SMEM table) and
the receive leg's fused ``dps_wire_reduce`` decodes + means the int8
payload without an fp32 ``(n, chunk)`` intermediate in HBM.

All collective functions here are written for ``shard_map`` bodies: they
take an ``axis_name`` and use raw ``lax`` collectives.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tagging
from repro.core.fixed_point import (FixedPointFormat, QuantStats,
                                    ROUND_NEAREST, ROUND_STOCHASTIC, exp2_int,
                                    wire_quantize)
from repro.device import on_tpu

# int8 wire capacity: IL + FL beyond this saturates grid integers.
WIRE_BITS = 8

# Elements per grouped-kernel grid tile: the group-aligned layout pads every
# group to a multiple of this (and rank chunks to tile multiples), so a tile
# never straddles groups.  Must be a multiple of
# ``repro.kernels.dps_quant.MIN_GROUP_QUANTUM`` (= 32·128, the minimum int8
# TPU tile); bigger quanta trade padding overhead for fewer grid steps —
# benchmarks pass a larger one for multi-MiB tensors.
WIRE_GROUP_QUANTUM = 4096

# The jnp codec has no (32, 128) tile constraint — its layout granularity
# only needs the int8 lane width, so tiny models can run much finer grouped
# layouts without the kernel backend's per-group padding floor.
WIRE_JNP_TILE = 128


def default_wire_quantum(size: int, groups: int, backend: str) -> int:
    """Size-aware grouped-wire quantum: ``ceil(size / G)`` rounded up to
    the backend's int8 tile, capped at :data:`WIRE_GROUP_QUANTUM`.

    The ``kernel`` backend's grid tile must stay a multiple of the
    (32, 128) minimum int8 TPU tile (= ``WIRE_GROUP_QUANTUM``), so it
    always resolves the classic 4096.  The ``jnp`` backend only needs
    lane-width (:data:`WIRE_JNP_TILE`) alignment, so a tiny model's
    per-group padding shrinks from 4096·G to ~``size`` elements.  The
    per-element collective results are layout-invariant (rounding bits are
    drawn per *element*, receive-leg sums are exact in the fp32 mantissa),
    so the two backends stay bit-identical even when they resolve
    different quanta.
    """
    tile = WIRE_GROUP_QUANTUM if backend == "kernel" else WIRE_JNP_TILE
    target = -(-max(size, 1) // max(groups, 1))
    return min(WIRE_GROUP_QUANTUM, max(tile, -(-target // tile) * tile))


def _resolve_quantum(quantum: Optional[int], size: int, groups: int,
                     backend: str) -> int:
    """An explicit ``quantum=`` wins; ``None`` derives the size-aware
    default for the resolved backend."""
    if quantum is not None:
        return int(quantum)
    return default_wire_quantum(size, groups, backend)


def wire_format(fmt: FixedPointFormat, wire_bits: int = WIRE_BITS
                ) -> FixedPointFormat:
    """Derive a wire ⟨IL, FL⟩ from a (wider) compute format.

    Keeps the radix position — IL, the overflow guard — and spends the
    remaining ``wire_bits`` on fraction: ``⟨min(IL, wire_bits - 1),
    wire_bits - IL⟩``.

    NOTE: the training loop no longer derives its wire formats this way —
    each wire leg's ⟨IL, FL⟩ now comes from a dedicated precision domain
    (``wire_grads`` / ``wire_params``) in the :class:`PrecisionPlan`
    registry, because a controller that moves IL in response to wire
    overflow moves the wire radix with it, and under hair-trigger
    ``r_max`` that ratchet destabilizes training (dist/README.md).  The
    helper remains for deriving *static* wire formats in tools and tests.
    """
    if not 2 <= wire_bits <= WIRE_BITS:
        raise ValueError(f"wire_bits must be in [2, {WIRE_BITS}] for an int8 "
                         f"payload, got {wire_bits}")
    il = jnp.clip(jnp.asarray(fmt.il, jnp.int32), 1, wire_bits - 1)
    return FixedPointFormat(il, (wire_bits - il).astype(jnp.int32))


def resolve_domain_format(formats, domain: str) -> FixedPointFormat:
    """One collective leg's ⟨IL, FL⟩ from a precision-domain registry.

    ``formats`` is either the ``{domain: FixedPointFormat}`` mapping
    produced by ``qtrain.bundle_formats`` — the leg picks out its own
    domain — or a bare :class:`FixedPointFormat`, used as-is (the
    pre-registry calling convention, kept for benchmarks and direct
    codec tests).
    """
    if isinstance(formats, FixedPointFormat):
        return formats
    try:
        fmt = formats[domain]
    except (KeyError, IndexError, TypeError):
        have = sorted(formats) if hasattr(formats, "keys") else type(formats)
        raise KeyError(
            f"no {domain!r} format in the registry mapping (have {have}); "
            "declare the wire domain in the PrecisionPlan or pass a "
            "FixedPointFormat directly") from None
    if not isinstance(fmt, FixedPointFormat):
        raise TypeError(f"registry entry {domain!r} is {type(fmt)}, "
                        "expected FixedPointFormat")
    return fmt


def _concrete_ilfl(fmt: FixedPointFormat):
    """(il, fl) as numpy when statically known, else None (traced)."""
    if isinstance(fmt.il, jax.core.Tracer) or isinstance(fmt.fl, jax.core.Tracer):
        return None
    return np.asarray(fmt.il), np.asarray(fmt.fl)


def _validate_capacity(fmt: FixedPointFormat):
    """Raise eagerly on statically over-wide formats (IL + FL > 8).

    Traced formats can't be rejected at trace time; for those the encode
    saturates at ±127 and counts the saturated elements into
    ``QuantStats.overflow`` so the controller sees the wire clipping.
    """
    conc = _concrete_ilfl(fmt)
    if conc is None:
        return
    il, fl = conc
    total = il.astype(np.int64) + fl.astype(np.int64)
    if np.any(total > WIRE_BITS):
        raise ValueError(
            f"⟨IL, FL⟩ = ⟨{il}, {fl}⟩ exceeds the int8 wire: IL + FL = "
            f"{total} > {WIRE_BITS}.  Grid integers would saturate at ±127; "
            f"derive a wire format with wire_format(fmt) instead.")


def _group_layout(size: int, groups: int) -> Tuple[int, int]:
    """(chunk, pad) splitting ``size`` elements into ``groups`` chunks."""
    chunk = -(-size // groups)
    return chunk, groups * chunk - size


def _equal_group_sizes(size: int, groups: int) -> Tuple[int, ...]:
    """The default [G] split: equal ``ceil(size / G)`` contiguous chunks
    (the last possibly short or empty)."""
    chunk = -(-size // groups)
    return tuple(max(0, min(chunk, size - g * chunk)) for g in range(groups))


@dataclasses.dataclass(frozen=True)
class GroupLayout:
    """Static group-aligned flat layout shared by kernels and collectives.

    Group ``g``'s payload occupies ``[offsets[g], offsets[g] +
    group_sizes[g])`` of the aligned buffer; the slot is padded to a
    multiple of ``quantum`` (one grouped-kernel grid tile), so a tile
    never straddles groups.  The buffer is then padded to ``n_chunks``
    equal, tile-aligned ``chunk``-element rank chunks (``total = n_chunks
    · chunk``), so an ``all_to_all``/``all_gather`` boundary always falls
    on a tile boundary and every tile's format is resolvable from the
    ``[G, 2]`` table through :meth:`tile_groups`.  All fields are Python
    ints — the layout is part of the jit closure, never traced.
    """

    group_sizes: Tuple[int, ...]
    quantum: int
    n_chunks: int
    padded: Tuple[int, ...]
    offsets: Tuple[int, ...]
    chunk: int
    total: int

    @property
    def size(self) -> int:
        return sum(self.group_sizes)

    @property
    def tiles(self) -> int:
        return self.total // self.quantum

    @property
    def is_exact(self) -> bool:
        """True when every group already sits at its aligned offset and no
        tail padding exists — align/dealign are then identities (layer
        sizes that are quantum multiples, the common big-model case)."""
        return self.total == self.size and all(
            p == s for p, s in zip(self.padded, self.group_sizes))

    def tile_groups(self) -> np.ndarray:
        """int32 ``[tiles]`` tile → group row (tail padding reads row 0,
        which the mask keeps out of wire bytes and statistics)."""
        out = np.zeros((self.tiles,), np.int32)
        for g, (off, pad) in enumerate(zip(self.offsets, self.padded)):
            out[off // self.quantum:(off + pad) // self.quantum] = g
        return out

    def mask(self) -> jax.Array:
        """float32 ``[total]`` validity (1 on payload, 0 on padding).

        Built on the device from per-tile payload counts: a host array of
        ``total`` floats would enter the compiled step as a constant as
        large as the whole gradient."""
        valid = np.zeros((self.tiles,), np.int32)
        for off, size in zip(self.offsets, self.group_sizes):
            t0, full = off // self.quantum, size // self.quantum
            valid[t0:t0 + full] = self.quantum
            if size % self.quantum:
                valid[t0 + full] = size % self.quantum
        lane = jax.lax.broadcasted_iota(jnp.int32,
                                        (self.tiles, self.quantum), 1)
        return (lane < jnp.asarray(valid)[:, None]).astype(
            jnp.float32).reshape(self.total)

    def align(self, flat: jax.Array) -> jax.Array:
        """Contiguous ``[size]`` payload → aligned ``[total]`` buffer
        (padding zero-filled; the no-op copy is skipped when the layout
        is already exact)."""
        if self.is_exact:
            return flat
        out = jnp.zeros((self.total,), flat.dtype)
        off_in = 0
        for off, size in zip(self.offsets, self.group_sizes):
            if size:
                out = jax.lax.dynamic_update_slice(
                    out, flat[off_in:off_in + size], (off,))
            off_in += size
        return out

    def dealign(self, aligned: jax.Array) -> jax.Array:
        """Aligned ``[total]`` buffer → contiguous ``[size]`` payload."""
        if self.is_exact:
            return aligned
        parts = [aligned[off:off + size]
                 for off, size in zip(self.offsets, self.group_sizes) if size]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def group_layout(group_sizes, n_chunks: int = 1,
                 quantum: int = WIRE_GROUP_QUANTUM) -> GroupLayout:
    """Build the group-aligned layout for ``group_sizes`` payload groups
    split across ``n_chunks`` ranks."""
    sizes = tuple(int(s) for s in group_sizes)
    if any(s < 0 for s in sizes):
        raise ValueError(f"negative group size in {sizes}")
    padded = tuple(-(-s // quantum) * quantum for s in sizes)
    offsets, off = [], 0
    for p in padded:
        offsets.append(off)
        off += p
    chunk = quantum * -(-off // (n_chunks * quantum)) if off else quantum
    return GroupLayout(group_sizes=sizes, quantum=quantum, n_chunks=n_chunks,
                       padded=padded, offsets=tuple(offsets), chunk=chunk,
                       total=chunk * n_chunks)


def _group_ids(group_sizes) -> np.ndarray:
    """int32 per-element group id for a contiguous (unaligned) split."""
    return np.repeat(np.arange(len(group_sizes), dtype=np.int32),
                     np.asarray(group_sizes, np.int64))


def _check_group_sizes(fmt: FixedPointFormat, group_sizes, total: int,
                       what: str = "x.size"):
    """``group_sizes`` (when given) must have one entry per format-table
    row and sum to the payload size — a mismatched table would otherwise
    be consumed silently with wrong formats (or, on the kernel path, read
    past the [G, 2] SMEM table)."""
    if group_sizes is None:
        return
    groups = fmt.il.shape[0]
    if len(group_sizes) != groups or sum(group_sizes) != total:
        raise ValueError(
            f"group_sizes {tuple(group_sizes)} must have {groups} entries "
            f"(one per format-table row) summing to {what} = {total}")


def wire_all_to_all(payload: jax.Array, axis_name) -> jax.Array:
    """Tiled ``all_to_all`` of an ``(n, chunk)`` payload over its rows.

    The payload travels as ``(n, chunk / lanes, lanes)`` blocks when a
    128-multiple lane width divides the chunk: the TPU compiler's
    all-to-all of a 2-D int8 array takes compile time and host memory
    linear in the chunk (a minute and several GB at 3.4e7 elements), the
    3-D form compiles in about a second.  Same bytes, same order."""
    n, chunk = payload.shape
    lanes = next((w for w in (1024, 128) if chunk % w == 0), None)
    if lanes is None:
        return jax.lax.all_to_all(payload, axis_name, split_axis=0,
                                  concat_axis=0, tiled=True)
    out = jax.lax.all_to_all(payload.reshape(n, chunk // lanes, lanes),
                             axis_name, split_axis=0, concat_axis=0,
                             tiled=True)
    return out.reshape(n, chunk)


def _resolve_backend(backend: str) -> str:
    if backend == "auto":
        return "kernel" if on_tpu() else "jnp"
    if backend not in ("kernel", "jnp"):
        raise ValueError(f"unknown wire codec backend {backend!r}; "
                         "expected 'auto', 'kernel' or 'jnp'")
    return backend


def _segment_stats(s: QuantStats, ids, groups: int) -> QuantStats:
    """Per-tile/per-element QuantStats → ``[G]`` rows via segment reduce."""
    seg = lambda v: jax.ops.segment_sum(v, ids, num_segments=groups)
    return QuantStats(
        count=seg(s.count), nonzero=seg(s.nonzero), overflow=seg(s.overflow),
        abs_err_sum=seg(s.abs_err_sum), rel_err_sum=seg(s.rel_err_sum),
        abs_sum=seg(s.abs_sum),
        max_abs=jnp.maximum(
            jax.ops.segment_max(s.max_abs, ids, num_segments=groups), 0.0))


def _encode_aligned(x_al: jax.Array, fmt: FixedPointFormat, tile_group,
                    mask, *, bits=None, key=None, mode: str,
                    backend: str, quantum: int, compute_stats: bool = True):
    """Grouped wire encode of a group-aligned ``[total]`` buffer.

    One fused kernel launch on the ``kernel`` backend (``[G, 2]`` SMEM
    table, ``[G, N_STATS]`` accumulator); per-tile ``wire_quantize`` plus
    a segment reduction on ``jnp`` — bit-exact wire bytes either way.
    Returns ``(wire int8 [total], [G]-shaped stats | None)``.
    """
    stochastic = mode == ROUND_STOCHASTIC
    if stochastic and bits is None:
        if key is None:
            raise ValueError("stochastic rounding needs `bits` or `key`")
        bits = jax.random.bits(key, shape=(x_al.size,), dtype=jnp.uint32)
    x_al = tagging.tag(x_al, "encode_in", stochastic=stochastic)
    if stochastic:
        bits = tagging.tag(bits, "sr_bits")
    if backend == "kernel":
        from repro.kernels import ops
        return ops.dps_quantize_wire_grouped(
            x_al, fmt, tile_group,
            bits=bits if stochastic else None, mask=mask,
            stochastic=stochastic, quantum=quantum,
            compute_stats=compute_stats)
    tiles = x_al.size // quantum
    tg = jnp.asarray(tile_group, jnp.int32)
    fmt_t = FixedPointFormat(fmt.il[tg], fmt.fl[tg])
    wire, s = wire_quantize(
        x_al.reshape(tiles, quantum), fmt_t, mode=mode,
        bits=bits.reshape(tiles, quantum) if bits is not None else None,
        compute_stats=compute_stats,
        mask=mask.reshape(tiles, quantum) if mask is not None else None)
    stats = (_segment_stats(s, tg, fmt.il.shape[0]) if compute_stats
             else None)
    return wire.reshape(-1), stats


def _wire_reduce(wire: jax.Array, fmt: FixedPointFormat, tile_group,
                 *, backend: str, quantum: int) -> jax.Array:
    """Receive leg: ``(n, chunk)`` int8 → fp32 ``[chunk]`` mean.

    The ``kernel`` backend runs the fused ``dps_wire_reduce`` (no fp32
    ``(n, chunk)`` intermediate in HBM); ``jnp`` decodes per tile and
    means.  Every decoded value is an exact fp32 multiple of its group's
    ``2^-FL`` and the sums stay inside the fp32 mantissa, so both
    backends produce bit-identical means.
    """
    n = wire.shape[0]
    if backend == "kernel":
        from repro.kernels import ops
        return ops.dps_wire_reduce(wire, fmt, tile_group, quantum=quantum)
    if fmt.il.ndim == 0:
        return wire_decode(wire, fmt).sum(axis=0) / n
    tiles = wire.shape[1] // quantum
    inv = exp2_int(-fmt.fl)[jnp.asarray(tile_group, jnp.int32)]
    dec = wire.reshape(n, tiles, quantum).astype(jnp.float32) * inv[None, :,
                                                                    None]
    return (dec.sum(axis=0) / n).reshape(-1)


def _decode_aligned(wire_al: jax.Array, fmt: FixedPointFormat, tile_group,
                    quantum: int, dtype=jnp.float32) -> jax.Array:
    """Aligned ``[total]`` int8 → values, per-tile FL from the table."""
    tiles = wire_al.size // quantum
    inv = exp2_int(-fmt.fl)[jnp.asarray(tile_group, jnp.int32)]
    dec = wire_al.reshape(tiles, quantum).astype(jnp.float32) * inv[:, None]
    return tagging.tag(dec.reshape(-1).astype(dtype), "decode_out")


def _encode_elementwise(x: jax.Array, fmt: FixedPointFormat, elem_group,
                        *, bits=None, key=None, mode: str,
                        compute_stats: bool = True):
    """Grouped encode with per-ELEMENT group ids (no alignment assumed).

    The layout-agnostic jnp path for unequal ``group_sizes`` and for
    collectives whose chunk layout is owned by the caller (the ZeRO
    halves): formats are gathered per element, stats segment-reduce into
    ``[G]`` rows.  Wire bytes are bit-identical to the aligned kernel
    path (same elementwise math, same rounding bits per element).  The
    per-element stat terms exist only as fusion inputs to the segment
    reductions under jit (XLA fuses the elementwise producers into the
    scatter-adds); this is the correctness-grade grouped path — the hot
    paths run :func:`_encode_aligned`'s tile-granular reduction instead.
    """
    gid = jnp.asarray(elem_group, jnp.int32)
    fmt_e = FixedPointFormat(fmt.il[gid], fmt.fl[gid])
    if mode == ROUND_STOCHASTIC and bits is None:
        if key is None:
            raise ValueError("stochastic rounding needs `bits` or `key`")
        bits = jax.random.bits(key, shape=(x.size,), dtype=jnp.uint32)
    x = tagging.tag(x, "encode_in", stochastic=mode == ROUND_STOCHASTIC)
    if bits is not None:
        bits = tagging.tag(bits, "sr_bits")
    wire, s = wire_quantize(x.reshape(-1), fmt_e, mode=mode,
                            bits=bits.reshape(-1) if bits is not None
                            else None,
                            compute_stats=compute_stats)
    stats = (_segment_stats(s, gid, fmt.il.shape[0]) if compute_stats
             else None)
    return wire, stats


def wire_encode(x: jax.Array, fmt: FixedPointFormat, *,
                key: Optional[jax.Array] = None,
                bits: Optional[jax.Array] = None,
                mode: str = ROUND_STOCHASTIC,
                compute_stats: bool = True,
                backend: str = "auto",
                group_sizes: Optional[Tuple[int, ...]] = None,
                ) -> Tuple[jax.Array, Optional[QuantStats]]:
    """Quantize ``x`` onto the ⟨IL, FL⟩ grid and emit int8 grid integers.

    Statically over-wide formats (IL + FL > 8) raise eagerly; traced
    formats saturate at ±127 with the saturated count folded into
    ``stats.overflow``.  ``bits`` (uint32, x.size elements) supplies the
    rounding noise deterministically; ``key`` draws it.

    Per-group formats (``fmt.il.shape == [G]``): the flattened ``x`` is
    split into G contiguous chunks — equal ``ceil(x.size / G)`` chunks by
    default (the last possibly short), or explicit per-layer
    ``group_sizes`` (must sum to ``x.size``) — and chunk g is encoded
    with ⟨IL[g], FL[g]⟩; stats come back with shape ``[G]``.  The
    round-trip is element-exact with G independent global-format calls on
    the chunks (given the same ``bits`` slices).  On the ``kernel``
    backend the grouped encode is ONE fused launch: the payload is
    scattered into the group-aligned layout (:class:`GroupLayout`), the
    kernel resolves each tile's format from the ``[G, 2]`` SMEM table,
    and the wire comes back in ``x``'s own layout.

    ``backend``: "auto" (fused Pallas kernel on TPU, jnp elsewhere),
    "kernel", or "jnp".  Both are bit-exact against
    ``repro.kernels.ref.dps_quant_wire_ref``.

    Returns ``(wire int8 with x's shape, stats)``.
    """
    if mode not in (ROUND_STOCHASTIC, ROUND_NEAREST):
        # reject here so both backends fail identically (the kernel path
        # folds mode into a boolean and would otherwise silently round
        # to nearest)
        raise ValueError(f"unknown rounding mode {mode!r}")
    _validate_capacity(fmt)
    x = tagging.tag(x, "encode_in", stochastic=mode == ROUND_STOCHASTIC)
    if bits is not None:
        bits = tagging.tag(bits, "sr_bits")
    if fmt.il.ndim == 0:
        if group_sizes is not None:
            raise ValueError("group_sizes needs a [G]-shaped format")
        if _resolve_backend(backend) == "kernel":
            from repro.kernels import ops
            stochastic = mode == ROUND_STOCHASTIC
            b = bits.reshape(-1) if bits is not None else None
            wire, stats = ops.dps_quantize_wire(x, fmt, key=key, bits=b,
                                                stochastic=stochastic)
            return wire, (stats if compute_stats else None)
        if bits is not None:
            bits = bits.reshape(x.shape)
        return wire_quantize(x, fmt, mode=mode, key=key, bits=bits,
                             compute_stats=compute_stats)

    # --- per-group path ---
    if fmt.il.ndim != 1:
        raise ValueError(f"per-group formats must be rank-1 [G], got shape "
                         f"{fmt.il.shape}")
    groups = fmt.il.shape[0]
    n = x.size
    if group_sizes is not None:
        group_sizes = tuple(int(s) for s in group_sizes)
        _check_group_sizes(fmt, group_sizes, n)
    if bits is None and mode == ROUND_STOCHASTIC:
        if key is None:
            raise ValueError("stochastic rounding needs `bits` or `key`")
        bits = tagging.tag(
            jax.random.bits(key, shape=(n,), dtype=jnp.uint32), "sr_bits")

    if _resolve_backend(backend) == "kernel":
        # one fused launch over the group-aligned layout; bits travel with
        # their elements, so the wire is bit-identical to the jnp path.
        layout = group_layout(group_sizes or _equal_group_sizes(n, groups))
        wire_al, stats = _encode_aligned(
            layout.align(x.reshape(-1)), fmt, jnp.asarray(layout.tile_groups()),
            layout.mask(),
            bits=layout.align(bits) if bits is not None else None,
            mode=mode, backend="kernel", quantum=layout.quantum,
            compute_stats=compute_stats)
        return layout.dealign(wire_al).reshape(x.shape), stats

    if group_sizes is not None:
        wire, stats = _encode_elementwise(x, fmt, _group_ids(group_sizes),
                                          bits=bits, mode=mode,
                                          compute_stats=compute_stats)
        return wire.reshape(x.shape), stats

    chunk, pad = _group_layout(n, groups)
    xg = _pad_reshape(x.reshape(-1), pad, (groups, chunk))
    bg = (_pad_reshape(bits.reshape(-1), pad, (groups, chunk))
          if bits is not None else None)
    mask = (None if not pad else
            _pad_reshape(jnp.ones((n,), jnp.float32), pad, (groups, chunk)))
    wire, stats = wire_quantize(xg, fmt, mode=mode, bits=bg,
                                compute_stats=compute_stats, mask=mask)
    return wire.reshape(-1)[:n].reshape(x.shape), stats


def _pad_reshape(v: jax.Array, pad: int, shape) -> jax.Array:
    """Tail-pad + reshape, skipping the no-op pad copy when ``pad == 0``."""
    return (v if not pad else jnp.pad(v, (0, pad))).reshape(shape)


def wire_decode(wire: jax.Array, fmt: FixedPointFormat,
                dtype=jnp.float32,
                group_sizes: Optional[Tuple[int, ...]] = None) -> jax.Array:
    """Grid integers (int8) back to values: ``wire * 2^-FL``.

    Accepts the same scalar or ``[G]``-shaped formats (and the same
    ``group_sizes`` split) as :func:`wire_encode` over the flattened
    payload.
    """
    if fmt.il.ndim == 0:
        dec = (wire.astype(jnp.float32) * exp2_int(-fmt.fl)).astype(dtype)
        return tagging.tag(dec, "decode_out")
    groups = fmt.il.shape[0]
    n = wire.size
    if group_sizes is not None:
        gid = jnp.asarray(_group_ids(group_sizes), jnp.int32)
        dec = wire.reshape(-1).astype(jnp.float32) * exp2_int(-fmt.fl)[gid]
        return tagging.tag(dec.reshape(wire.shape).astype(dtype), "decode_out")
    chunk, pad = _group_layout(n, groups)
    wg = _pad_reshape(wire.reshape(-1), pad, (groups, chunk))
    dec = wg.astype(jnp.float32) * exp2_int(-fmt.fl)[:, None]
    return tagging.tag(dec.reshape(-1)[:n].reshape(wire.shape).astype(dtype),
                       "decode_out")


def psum_stats(stats: QuantStats, axis_name) -> QuantStats:
    """Combine per-rank :class:`QuantStats` across ``axis_name``.

    Sums psum; ``max_abs`` pmaxes — matching ``QuantStats.merge``."""
    summed = jax.lax.psum((stats.count, stats.nonzero, stats.overflow,
                           stats.abs_err_sum, stats.rel_err_sum,
                           stats.abs_sum), axis_name)
    return QuantStats(*summed, max_abs=jax.lax.pmax(stats.max_abs, axis_name))


def dps_allreduce_mean(x: jax.Array, formats, axis_name,
                       key: jax.Array, *, mode: str = ROUND_STOCHASTIC,
                       backend: str = "auto", domain: str = "wire_grads",
                       group_sizes: Optional[Tuple[int, ...]] = None,
                       quantum: Optional[int] = None,
                       ) -> Tuple[jax.Array, QuantStats]:
    """Mean of per-rank ``x`` over ``axis_name`` with an int8 wire format.

    Reduce-scatter / all-gather decomposition, both legs compressed:

      1. each rank quantizes its full local tensor to the ⟨IL, FL⟩ grid and
         ships int8 grid integers through a tiled ``all_to_all`` — rank j
         ends up owning every rank's j-th chunk (reduce-scatter leg);
      2. the owner sums its chunks in fp32, divides by the axis size,
         re-quantizes the mean chunk and ``all_gather``s int8 back out.

    Total wire bytes ≈ 2·|x|·1 B vs 2·|x|·4 B for an fp32 ring all-reduce.
    With stochastic rounding each leg's error is < one grid step (2^-FL),
    so the result is within two grid steps of the exact mean and unbiased.

    A ``[G]``-shaped format runs one ⟨IL, FL⟩ per contiguous group
    (``group_sizes``, default equal chunks) through BOTH legs: the payload
    travels in the group-aligned layout (:class:`GroupLayout`, tile
    ``quantum``-aligned groups and rank chunks), so on the ``kernel``
    backend leg 1 is one grouped-kernel launch, the receive leg is the
    fused ``dps_wire_reduce`` (the fp32 ``(n, chunk)`` intermediate never
    touches HBM), and leg 2 re-encodes each owner's chunk with the
    per-tile formats.  Stats come back ``[G]``-shaped.

    ``backend`` selects the wire codec (see :func:`wire_encode`);
    ``formats``/``domain`` resolve the leg's ⟨IL, FL⟩ out of a
    precision-domain registry mapping (:func:`resolve_domain_format`).

    Returns ``(mean, stats)``; ``stats`` describe this rank's dispatch-leg
    quantization of the |x| local elements (so ``psum_stats(stats, axis)``
    counts each global element exactly once) and belong to the wire
    domain's controller.  Must run inside ``shard_map``; ``key`` may be
    identical across ranks (it is decorrelated with ``axis_index`` here).

    ``quantum=None`` (the default) derives the grouped layout's tile size
    per :func:`default_wire_quantum` — size-aware on the jnp backend, the
    kernel tile minimum on TPU; the result is layout-invariant either way.
    """
    fmt = resolve_domain_format(formats, domain)
    _validate_capacity(fmt)
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    k1, k2 = jax.random.split(jax.random.fold_in(key, idx))
    be = _resolve_backend(backend)
    shape, size = x.shape, x.size
    groups = fmt.il.shape[0] if fmt.il.ndim else 1
    q = _resolve_quantum(quantum, size, groups, be)

    with tagging.domain(domain):
        if fmt.il.ndim != 0:
            _check_group_sizes(fmt, group_sizes, size)
            layout = group_layout(group_sizes
                                  or _equal_group_sizes(size, groups),
                                  n_chunks=n, quantum=q)
            # leg-2 bits are element-indexed, so every rank must derive
            # the same stream (see _aligned_allreduce_mean): a rank-
            # invariant fold distinct from every leg-1 fold_in(key, idx)
            k2s = jax.random.fold_in(key, 0x4C454732)        # "LEG2"
            mean_al, stats = _aligned_allreduce_mean(
                layout.align(x.reshape(-1).astype(jnp.float32)), fmt, layout,
                axis_name, jax.random.fold_in(key, idx), k2s,
                mode=mode, backend=be)
            stats = tagging.tag_tree(stats, "wire_stats")
            return (layout.dealign(mean_al).reshape(shape).astype(x.dtype),
                    stats)

        chunk, pad = _group_layout(size, n)

        # leg 1: quantize the local tensor (stats cover exactly these
        # elements), pad the int8 wire, and scatter chunk j to rank j.
        wire, stats = wire_encode(x.reshape(-1), fmt, key=k1, mode=mode,
                                  backend=be)
        wire = _pad_reshape(wire, pad, (n, chunk))
        wire = tagging.tag(wire, "wire_payload", leg="dispatch")
        wire = wire_all_to_all(wire, axis_name)    # (n, chunk)
        # receive: fused int8 decode-reduce on the kernel backend — the
        # decoded fp32 (n, chunk) intermediate never exists in HBM.
        part = _wire_reduce(wire, fmt, None, backend=be, quantum=q)

        # leg 2: re-quantize the owned mean chunk, gather int8 everywhere.
        wire2, _ = wire_encode(part, fmt, key=k2, mode=mode,
                               compute_stats=False, backend=be)
        wire2 = tagging.tag(wire2, "wire_payload", leg="gather")
        full = jax.lax.all_gather(wire2, axis_name, axis=0, tiled=True)
        mean = wire_decode(full, fmt, x.dtype)[:size].reshape(shape)
        return mean, tagging.tag_tree(stats, "wire_stats")


def _leg2_bits(k2, group_sizes, group_offset: int = 0) -> jax.Array:
    """Rank-invariant gather-leg rounding bits, keyed by GLOBAL group index.

    Element e of group ``group_offset + g`` always draws the same uint32 —
    no matter which layout (monolithic, per-bucket, sharded) carries the
    group — because each group gets its own ``fold_in(k2, global_g)``
    stream, mirroring the dispatch leg's per-leaf ``fold_in(k1, g)``
    draws.  This is what makes the bucketed pipeline and the sharded ZeRO
    halves bit-exact with the monolithic collective under stochastic
    rounding.  Returns the contiguous ``[sum(group_sizes)]`` stream.
    """
    streams = [jax.random.bits(jax.random.fold_in(k2, group_offset + g),
                               shape=(s,), dtype=jnp.uint32)
               for g, s in enumerate(group_sizes) if s]
    return streams[0] if len(streams) == 1 else jnp.concatenate(streams)


def _aligned_rs_snap(x_al, fmt: FixedPointFormat,
                     layout: GroupLayout, axis_name, k1, k2,
                     *, mode: str, backend: str, group_offset: int = 0,
                     encode_leg1=None):
    """Compressed reduce-scatter + wire-grid snap of an aligned buffer.

    The first half of :func:`_aligned_allreduce_mean`, usable on its own
    as the ZeRO-1 gradient half: dispatch-leg encode, tiled
    ``all_to_all``, fused decode-reduce of the owned chunk, then a LOCAL
    re-encode of the mean chunk onto the wire grid (no collective — the
    int8 ``wire2`` only travels if the caller gathers it).  Because the
    all-reduce decodes exactly this ``wire2`` after its gather, a sharded
    consumer that decodes ``wire2`` locally sees bit-identical values to
    its chunk of the gathered mean — the property that makes ZeRO +
    per-layer wire bit-exact with the replicated step.

    ``encode_leg1(tile_groups, mask) -> (wire_al, stats)`` overrides the
    dispatch-leg encode (the tree collectives encode leaf-by-leaf into a
    preallocated buffer instead of scattering an fp32 copy); the default
    runs :func:`_encode_aligned` on ``x_al``.

    Rounding bits on both legs are drawn per **element** and keyed by
    global group index — leg 1 via the caller's per-leaf ``fold_in(k1,
    g)`` draws (or one ``[layout.size]`` stream in the default encode),
    leg 2 via :func:`_leg2_bits` with ``group_offset`` naming the first
    group's global index — so the per-element result is invariant to the
    layout's quantum, rank-chunk and bucket geometry (receive-leg sums
    are exact in the fp32 mantissa), and the two backends stay
    bit-identical even when they resolve different default quanta.
    ``k2`` must be identical on every rank (element → bits, not rank →
    bits); ``k1`` may be per-rank (leg 1 encodes rank-local data).

    Returns ``(part fp32 [chunk] raw mean, wire2 int8 [chunk], stats,
    my_tg)``.
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    tg_all = jnp.asarray(layout.tile_groups())
    mask = layout.mask()
    stochastic = mode == ROUND_STOCHASTIC
    if encode_leg1 is None:
        bits1 = (layout.align(jax.random.bits(k1, shape=(layout.size,),
                                              dtype=jnp.uint32))
                 if stochastic else None)
        wire_al, stats = _encode_aligned(
            x_al, fmt, tg_all, mask, bits=bits1, mode=mode, backend=backend,
            quantum=layout.quantum)
    else:
        wire_al, stats = encode_leg1(tg_all, mask)

    payload = tagging.tag(wire_al.reshape(n, layout.chunk), "wire_payload",
                          leg="dispatch")
    wire = wire_all_to_all(payload, axis_name)
    # this rank's chunk covers tiles [idx·tpc, (idx+1)·tpc) of the layout
    tpc = layout.chunk // layout.quantum
    my_tg = jax.lax.dynamic_slice(tg_all, (idx * tpc,), (tpc,))
    part = _wire_reduce(wire, fmt, my_tg, backend=backend,
                        quantum=layout.quantum)           # (chunk,) fp32

    # leg 2: per-tile re-encode of the owned mean chunk (stats not needed;
    # alignment padding is zero and encodes to zero bytes)
    if stochastic:
        bits2 = jax.lax.dynamic_slice(
            layout.align(_leg2_bits(k2, layout.group_sizes, group_offset)),
            (idx * layout.chunk,), (layout.chunk,))
    else:
        bits2 = None
    wire2, _ = _encode_aligned(part, fmt, my_tg, None, bits=bits2,
                               mode=mode, backend=backend,
                               quantum=layout.quantum, compute_stats=False)
    return part, wire2, stats, my_tg


def _aligned_allreduce_mean(x_al: jax.Array, fmt: FixedPointFormat,
                            layout: GroupLayout, axis_name, k1, k2,
                            *, mode: str, backend: str,
                            group_offset: int = 0, encode_leg1=None):
    """Both compressed legs over a group-aligned ``[total]`` fp32 buffer.

    :func:`_aligned_rs_snap` (dispatch, reduce, wire-grid re-encode of
    the owned mean chunk) followed by the int8 ``all_gather`` of the
    re-encoded chunks and the per-tile decode.  Returns ``(mean_al fp32
    [total], [G] stats)``; see :func:`_aligned_rs_snap` for the
    element-indexed rounding-bit contract.
    """
    _, wire2, stats, _ = _aligned_rs_snap(
        x_al, fmt, layout, axis_name, k1, k2, mode=mode, backend=backend,
        group_offset=group_offset, encode_leg1=encode_leg1)
    wire2 = tagging.tag(wire2, "wire_payload", leg="gather")
    full = jax.lax.all_gather(wire2, axis_name, axis=0, tiled=True)
    return _decode_aligned(full, fmt, jnp.asarray(layout.tile_groups()),
                           layout.quantum), stats


def dps_reduce_scatter_mean(x: jax.Array, formats, axis_name,
                            key: jax.Array, *, mode: str = ROUND_STOCHASTIC,
                            backend: str = "auto",
                            domain: str = "wire_grads",
                            group_sizes: Optional[Tuple[int, ...]] = None,
                            quantum: Optional[int] = None,
                            ) -> Tuple[jax.Array, QuantStats]:
    """Reduce-scatter mean over ``axis_name`` with the int8 wire on the
    scatter leg — the ZeRO half-collective.

    Each rank quantizes its *full* local tensor onto the ⟨IL, FL⟩ grid and
    ships int8 grid integers through a tiled ``all_to_all``, so rank j ends
    up holding every rank's j-th chunk; the owner decodes, sums in fp32 and
    divides by the axis size.  This is exactly leg 1 of
    :func:`dps_allreduce_mean` — but where the all-reduce immediately
    re-quantizes and gathers the mean back out, ZeRO-1 keeps it **sharded**
    so each rank can run its slice of the optimizer locally
    (:func:`dps_allgather_params` is the return leg, applied to the updated
    parameter shard instead of the gradient mean).

    Wire bytes ≈ |x|·1 B per rank vs |x|·4 B for an fp32 reduce-scatter;
    stochastic rounding keeps the leg unbiased with error < one grid step
    (2^-FL) on every element of the mean.

    A ``[G]``-shaped format splits the flattened ``x`` into contiguous
    groups (``group_sizes``, default equal chunks) and returns ``[G]``
    stats.  The chunk layout here is the CALLER's contract (the
    ``ZeroPartitioner`` flat slices), so the grouped codec runs
    per-element formats on the jnp path — group boundaries need not align
    with rank chunks.  The train step's grouped ZeRO path does NOT come
    through here: it runs the group-aligned
    :class:`repro.dist.sharding.GroupAlignedPartitioner` layout through
    :func:`repro.dist.overlap.zero_bucketed_reduce_scatter` (kernel-grade
    aligned codec, per-bucket collectives); this per-element form remains
    for callers that own their own chunk layout.

    Returns ``(shard, stats)``: ``shard`` is this rank's chunk of the
    flattened, zero-padded mean — shape ``[ceil(x.size / n)]``, the padded
    1-D layout of :class:`repro.dist.sharding.ZeroPartitioner` — and
    ``stats`` cover this rank's dispatch-leg encode of its |x| local
    elements (``psum_stats(stats, axis)`` counts each global element exactly
    once).  Must run inside ``shard_map``; ``key`` may be identical across
    ranks (it is decorrelated with ``axis_index`` here).
    ``formats``/``domain``: see :func:`resolve_domain_format`.
    ``quantum=None`` derives the receive-leg tile per
    :func:`default_wire_quantum`.
    """
    fmt = resolve_domain_format(formats, domain)
    _validate_capacity(fmt)
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    be = _resolve_backend(backend)
    chunk, pad = _group_layout(x.size, n)
    groups = fmt.il.shape[0] if fmt.il.ndim else 1
    q = _resolve_quantum(quantum, x.size, groups, be)

    with tagging.domain(domain):
        if fmt.il.ndim != 0:
            if backend == "kernel":
                raise ValueError(
                    "dps_reduce_scatter_mean runs [G]-shaped formats with "
                    "the per-element jnp codec (the shard layout is the "
                    "caller's ZeroPartitioner contract, so group boundaries "
                    "cannot be tile-aligned); an explicit backend='kernel' "
                    "request cannot be honored here — use backend='auto', "
                    "or dps_allreduce_mean for the group-aligned kernel "
                    "schedule")
            _check_group_sizes(fmt, group_sizes, x.size)
            gid = _group_ids(group_sizes
                             or _equal_group_sizes(x.size, fmt.il.shape[0]))
            wire, stats = _encode_elementwise(
                x.reshape(-1), fmt, gid, key=jax.random.fold_in(key, idx),
                mode=mode)
            wire = _pad_reshape(wire, pad, (n, chunk))
            wire = tagging.tag(wire, "wire_payload", leg="dispatch")
            wire = wire_all_to_all(wire, axis_name)
            # decode with the formats of THIS rank's chunk positions
            gid_pad = np.pad(gid, (0, pad))
            my_gid = jax.lax.dynamic_slice(jnp.asarray(gid_pad),
                                           (idx * chunk,), (chunk,))
            inv = exp2_int(-fmt.fl)[my_gid]
            shard = (wire.astype(jnp.float32) * inv[None, :]).sum(axis=0) / n
            return shard, tagging.tag_tree(stats, "wire_stats")

        wire, stats = wire_encode(x.reshape(-1), fmt,
                                  key=jax.random.fold_in(key, idx),
                                  mode=mode, backend=be)
        wire = _pad_reshape(wire, pad, (n, chunk))
        wire = tagging.tag(wire, "wire_payload", leg="dispatch")
        wire = wire_all_to_all(wire, axis_name)     # (n, chunk)
        # fused decode-reduce on the kernel backend (no fp32 (n, chunk)
        # in HBM)
        shard = _wire_reduce(wire, fmt, None, backend=be, quantum=q)
        return shard, tagging.tag_tree(stats, "wire_stats")


def dps_allgather_params(shard: jax.Array, formats, axis_name,
                         key: jax.Array, *, mode: str = ROUND_STOCHASTIC,
                         backend: str = "auto", domain: str = "wire_params",
                         group_sizes: Optional[Tuple[int, ...]] = None,
                         ) -> Tuple[jax.Array, QuantStats]:
    """All-gather per-rank parameter shards with an int8 wire — the ZeRO
    return leg.

    Each rank quantizes its updated shard (the slice of the flattened
    parameter vector it just stepped locally) onto the ⟨IL, FL⟩ grid, ships
    int8 grid integers through a tiled ``all_gather``, and every rank
    decodes the concatenation.  Wire bytes ≈ |shard|·1 B per rank vs
    |shard|·4 B fp32.  Note the decode quantizes the *parameters* onto the
    wire grid — the leg reads the registry's ``wire_params`` domain
    (:func:`resolve_domain_format`), whose controller tracks the weight
    range from the stats returned here, so wire clipping and rounding
    error steer next step's wire ⟨IL, FL⟩ without touching the compute
    weights controller.

    A ``[G]``-shaped format partitions the GATHERED ``[n · shard.size]``
    vector into contiguous groups (``group_sizes``, default equal
    chunks): each rank encodes its shard with the formats of its own
    positions and every rank decodes the concatenation group-wise.  The
    shard layout is the caller's contract, so the grouped codec runs
    per-element formats (jnp path) — no alignment assumed.  (The train
    step's grouped ZeRO return leg runs the group-aligned layout through
    :func:`repro.dist.overlap.zero_allgather_params` instead.)

    Returns ``(full, stats)``: ``full`` is the flat ``[n · shard.size]``
    gathered vector (identical on every rank), ``stats`` cover this rank's
    encode of its |shard| elements (``psum_stats`` → every global element
    counted exactly once).  Must run inside ``shard_map``; ``key`` may be
    identical across ranks.
    """
    fmt = resolve_domain_format(formats, domain)
    _validate_capacity(fmt)
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    with tagging.domain(domain):
        if fmt.il.ndim != 0:
            if backend == "kernel":
                raise ValueError(
                    "dps_allgather_params runs [G]-shaped formats with the "
                    "per-element jnp codec (the shard layout is the "
                    "caller's contract, so group boundaries cannot be "
                    "tile-aligned); an explicit backend='kernel' request "
                    "cannot be honored here — use backend='auto'")
            total = n * shard.size
            _check_group_sizes(fmt, group_sizes, total,
                               what="the gathered vector size")
            gid = _group_ids(group_sizes
                             or _equal_group_sizes(total, fmt.il.shape[0]))
            my_gid = jax.lax.dynamic_slice(jnp.asarray(gid),
                                           (idx * shard.size,),
                                           (shard.size,))
            wire, stats = _encode_elementwise(
                shard.reshape(-1), fmt, my_gid,
                key=jax.random.fold_in(key, idx), mode=mode)
            wire = tagging.tag(wire, "wire_payload", leg="gather")
            full = jax.lax.all_gather(wire, axis_name, axis=0, tiled=True)
            dec = tagging.tag(
                full.astype(jnp.float32)
                * exp2_int(-fmt.fl)[jnp.asarray(gid)], "decode_out")
            return dec, tagging.tag_tree(stats, "wire_stats")
        wire, stats = wire_encode(shard.reshape(-1), fmt,
                                  key=jax.random.fold_in(key, idx),
                                  mode=mode, backend=backend)
        wire = tagging.tag(wire, "wire_payload", leg="gather")
        full = jax.lax.all_gather(wire, axis_name, axis=0, tiled=True)
        return wire_decode(full, fmt), tagging.tag_tree(stats, "wire_stats")


def dps_allreduce_mean_tree(tree, formats, axis_name,
                            key: jax.Array, *, mode: str = ROUND_STOCHASTIC,
                            backend: str = "auto",
                            domain: str = "wire_grads",
                            quantum: Optional[int] = None,
                            payload_fault=None):
    """:func:`dps_allreduce_mean` over a whole pytree in ONE collective pair.

    Each leaf is encoded straight into its slot of ONE preallocated int8
    wire buffer (``dynamic_update_slice``; the old fp32
    flatten-and-concatenate pass over the whole tree is gone — the only
    tree-sized intermediate is the 4×-smaller int8 buffer), so the
    per-step gradient sync costs one all_to_all + one all_gather
    regardless of how many (possibly tiny) leaves the tree has — not 2·L
    launches each padded to the axis size.  The mean comes back leaf by
    leaf (int8 slice → decode → leaf dtype): the fp32 mean never exists
    as a flat tree-sized buffer either.

    A ``[G]``-shaped format (G = leaf count) runs ONE ⟨IL, FL⟩ PER LEAF:
    leaf g encodes into a :class:`GroupLayout`-aligned slot with
    ⟨IL[g], FL[g]⟩, both collective legs run group-aligned (fused grouped
    kernel + ``dps_wire_reduce`` on the ``kernel`` backend), and stats
    come back ``[G]``-shaped — per-layer wire formats at full kernel
    speed, one HBM pass per leg.

    Returns ``(mean_tree, stats)`` with every leaf cast back to its own
    dtype.  ``formats``/``domain``: see :func:`resolve_domain_format`.
    ``quantum=None`` derives the per-leaf slot alignment per
    :func:`default_wire_quantum` (size-aware on jnp, kernel tile on TPU).

    ``payload_fault`` is the fault-injection hook of
    ``repro.resilience.inject``: a callable applied to the encoded int8
    dispatch-leg buffer right before it enters the collective (simulating
    transport corruption), or None (the default — the jaxpr is
    unchanged).  Test harness only; the guards it exists to prove live in
    ``repro.resilience.guards``.
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
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    k1, k2 = jax.random.split(jax.random.fold_in(key, idx))
    be = _resolve_backend(backend)
    sizes = tuple(l.size for l in leaves)
    q = _resolve_quantum(quantum, sum(sizes),
                         len(leaves) if grouped else 1, be)

    if grouped:
        layout = group_layout(sizes, n_chunks=n, quantum=q)
        offsets, total = layout.offsets, layout.total
    else:
        # one format decodes everywhere, so exact packing (tail pad only,
        # no per-leaf alignment — plain offsets, not a GroupLayout, whose
        # invariants are tile-aligned) keeps the wire payload minimal.
        layout = None
        size = sum(sizes)
        chunk, _ = _group_layout(size, n)
        offsets = tuple(int(o) for o in np.cumsum((0,) + sizes[:-1]))
        total = chunk * n

    def encode_leg1(tg_all, mask):
        """Leaf-by-leaf encode into the preallocated int8 wire buffer."""
        buf = jnp.zeros((total,), jnp.int8)
        per_leaf = []
        for g, leaf in enumerate(leaves):
            fmt_g = (FixedPointFormat(fmt.il[g], fmt.fl[g]) if grouped
                     else fmt)
            w, s = wire_encode(leaf.reshape(-1), fmt_g,
                               key=jax.random.fold_in(k1, g), mode=mode,
                               backend=be)
            buf = jax.lax.dynamic_update_slice(buf, w, (offsets[g],))
            per_leaf.append(s)
        if grouped:
            stats = jax.tree.map(lambda *xs: jnp.stack(xs), *per_leaf)
        else:
            stats = per_leaf[0]
            for s in per_leaf[1:]:
                stats = stats.merge(s)
        if payload_fault is not None:
            buf = payload_fault(buf)
        return buf, stats

    with tagging.domain(domain):
        if grouped:
            # leg-2 bits are element-indexed (see _aligned_allreduce_mean):
            # every rank must derive the same stream
            k2s = jax.random.fold_in(key, 0x4C454732)        # "LEG2"
            mean_al, stats = _aligned_allreduce_mean(
                None, fmt, layout, axis_name, k1, k2s, mode=mode,
                backend=be, encode_leg1=encode_leg1)
            full = mean_al
            decode = lambda g, flat: flat  # already decoded per tile
        else:
            buf, stats = encode_leg1(None, None)
            payload = tagging.tag(buf.reshape(n, chunk), "wire_payload",
                                  leg="dispatch")
            wire = wire_all_to_all(payload, axis_name)
            part = _wire_reduce(wire, fmt, None, backend=be, quantum=q)
            # gather-leg bits keyed by global leaf index (rank-invariant
            # k2s stream, same contract as _aligned_rs_snap) so the
            # bucketed and sharded schedules stay bit-exact with this
            # monolithic one under stochastic rounding
            if mode == ROUND_STOCHASTIC:
                k2s = jax.random.fold_in(key, 0x4C454732)    # "LEG2"
                bits2 = jax.lax.dynamic_slice(
                    _pad_reshape(_leg2_bits(k2s, sizes), total - sum(sizes),
                                 (total,)),
                    (idx * chunk,), (chunk,))
            else:
                bits2 = None
            wire2, _ = wire_encode(part, fmt, bits=bits2, mode=mode,
                                   compute_stats=False, backend=be)
            wire2 = tagging.tag(wire2, "wire_payload", leg="gather")
            full = jax.lax.all_gather(wire2, axis_name, axis=0, tiled=True)
            decode = lambda g, sl: wire_decode(sl, fmt)
        stats = tagging.tag_tree(stats, "wire_stats")

    out = []
    for g, leaf in enumerate(leaves):
        sl = jax.lax.dynamic_slice(full, (offsets[g],), (leaf.size,))
        out.append(decode(g, sl).reshape(leaf.shape).astype(leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out), stats
