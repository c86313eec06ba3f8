"""jit'd public wrapper around the fused DPS quantization kernel.

``dps_quantize`` accepts any-rank tensors and a dynamic
:class:`~repro.core.fixed_point.FixedPointFormat`, reshapes to the kernel's
2-D tiling, and adapts the raw stats vector back into ``QuantStats``.

``interpret=None`` resolves from the platform: Mosaic on a TPU, the Pallas
interpreter elsewhere (the CPU tests).  ``onchip_prng=True`` selects the
PRNG-in-kernel variant (TPU only — see kernel docstring).

``dps_quantize_leaf`` is the train step's leaf quantizer on a TPU: one
fused kernel per weight or gradient leaf, on the leaf's own buffer.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.fixed_point import (ROUND_NEAREST, ROUND_STOCHASTIC,
                                    FixedPointFormat, QuantStats)
from repro.device import on_tpu
from repro.kernels import ref as ref_lib
from repro.kernels.dps_quant import (DEFAULT_BLOCK, DEFAULT_GROUP_QUANTUM,
                                     dps_quant_leaf_pallas, dps_quant_pallas,
                                     dps_quant_group_wire_pallas,
                                     dps_quant_wire_pallas,
                                     dps_wire_reduce_pallas, group_block)

# ---------------------------------------------------------------------------
# Static call-site geometry — what each wrapper WOULD launch, computed
# without tracing or executing anything.  ``repro.analysis.kernel_checks``
# builds one of these per Pallas call site reachable from a config and
# validates the tiling/SMEM invariants against
# ``dps_quant.KERNEL_SIGNATURES``.  The builders replicate the exact shape
# arithmetic of the wrappers below; keeping them in this module means a
# wrapper tiling change and its declared geometry are one diff.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class KernelCallGeometry:
    """One prospective Pallas launch, statically described."""

    kernel: str                       # KERNEL_SIGNATURES key
    grid: Tuple[int, ...]
    block: Tuple[int, int]            # (bm, bn) VMEM tile
    out_dtype: str
    num_scalar_prefetch: int          # arity at THIS call site
    scalar_shapes: Tuple[Tuple[int, ...], ...]   # prefetch operand shapes
    table_rows: Optional[int] = None  # G of the [G, 2] SMEM format table
    tile_group_len: Optional[int] = None         # T entries passed
    quantum: Optional[int] = None

    @property
    def smem_table_bytes(self) -> int:
        """int32 bytes of all scalar-prefetch operands at this site."""
        n = 0
        for shp in self.scalar_shapes:
            k = 1
            for d in shp:
                k *= d
            n += 4 * k
        return n


def quantize_call_geometry(size: int, *, block=None,
                           wire: bool = False) -> KernelCallGeometry:
    """Geometry of a :func:`dps_quantize` / :func:`dps_quantize_wire` call
    on a ``size``-element tensor (mirrors ``_fold_and_call`` +
    ``_pallas_quant``)."""
    block = block or DEFAULT_BLOCK
    minor = 1024 if size >= 1024 else max(size, 1)
    major = -(-size // minor)
    bm = min(block[0], major) if major % block[0] else block[0]
    bn = min(block[1], minor) if minor % block[1] else block[1]
    grid = (-(-major // bm), -(-minor // bn))
    return KernelCallGeometry(
        kernel="_kernel", grid=grid, block=(bm, bn),
        out_dtype="int8" if wire else "float32",
        num_scalar_prefetch=1, scalar_shapes=((3,),))


def group_wire_call_geometry(total: int, n_groups: int,
                             quantum: int = DEFAULT_GROUP_QUANTUM
                             ) -> KernelCallGeometry:
    """Geometry of a :func:`dps_quantize_wire_grouped` call on a
    group-aligned ``total``-element buffer with a ``[G, 2]`` table."""
    bm, bn = group_block(quantum)
    tiles = total // quantum
    return KernelCallGeometry(
        kernel="_group_kernel", grid=(tiles,), block=(bm, bn),
        out_dtype="int8", num_scalar_prefetch=3,
        scalar_shapes=((n_groups, 2), (tiles,), (1,)),
        table_rows=n_groups, tile_group_len=tiles, quantum=quantum)


def wire_reduce_call_geometry(n_ranks: int, chunk: int, n_groups: int,
                              quantum: int = DEFAULT_GROUP_QUANTUM
                              ) -> KernelCallGeometry:
    """Geometry of a :func:`dps_wire_reduce` call on an
    ``[n_ranks, chunk]`` payload (includes the internal tail pad)."""
    bm, bn = group_block(quantum)
    tiles = -(-chunk // quantum)
    return KernelCallGeometry(
        kernel="_wire_reduce_kernel", grid=(tiles,), block=(bm, bn),
        out_dtype="float32", num_scalar_prefetch=2,
        scalar_shapes=((n_groups, 2), (tiles,)),
        table_rows=n_groups, tile_group_len=tiles, quantum=quantum)


def paged_attn_call_geometry(batch_slots: int, pages_per_seq: int,
                             n_pages: int, page_size: int, kv_heads: int,
                             head_dim: int) -> KernelCallGeometry:
    """Geometry of a ``paged_attn_pallas`` decode launch (repro.serve).

    Grid is (batch slot, logical page slot); the VMEM tile is one gathered
    int8 KV page viewed as ``(page_size, kv_heads · head_dim)``, which must
    respect the (32, 128) int8 minimum; the SMEM residents are the (B, P)
    page table, the (n_pages, 2) per-page FL table and the (B,) lengths.
    ``quantum`` is the page's element count — also the grouped page-encode
    codec's quantum, so one declaration covers both launches' tiling.
    """
    return KernelCallGeometry(
        kernel="_paged_attn_kernel",
        grid=(batch_slots, pages_per_seq),
        block=(page_size, kv_heads * head_dim),
        out_dtype="float32",
        num_scalar_prefetch=3,
        scalar_shapes=((batch_slots, pages_per_seq), (n_pages, 2),
                       (batch_slots,)),
        table_rows=n_pages,
        tile_group_len=batch_slots * pages_per_seq,
        quantum=page_size * kv_heads * head_dim)


def bucketed_wire_call_geometries(bucket_leaf_sizes, n_ranks: int,
                                  quantum: int = DEFAULT_GROUP_QUANTUM
                                  ) -> Tuple[KernelCallGeometry, ...]:
    """Geometries of the kernel-backend launches ONE bucket of the
    backward-overlapped wire (``repro.dist.overlap``) would run: the
    grouped encode over the bucket's group-aligned buffer plus the fused
    decode-reduce on its ``(n_ranks, chunk)`` payload.  Mirrors the
    per-bucket ``group_layout`` arithmetic (each leaf padded to a quantum
    multiple, the total rounded up to ``n_ranks`` quantum-sized chunks),
    so a bucketed step's kernel schedule is checkable statically — G is
    the bucket's leaf count, not the whole tree's."""
    sizes = tuple(int(s) for s in bucket_leaf_sizes)
    padded = sum(-(-s // quantum) * quantum for s in sizes)
    chunk = (quantum * -(-padded // (n_ranks * quantum)) if padded
             else quantum)
    total = chunk * n_ranks
    return (group_wire_call_geometry(total, len(sizes), quantum),
            wire_reduce_call_geometry(n_ranks, chunk, len(sizes), quantum))


def _fold_and_call(pallas_fn, x, fmt, *, key, bits, stochastic, onchip_prng,
                   block, interpret):
    """Shared any-rank → 2-D tiling adapter around a dps_quant kernel."""
    if interpret is None:
        interpret = not on_tpu()
    orig_shape = x.shape
    n = x.size
    # fold to 2-D with a 128-lane-friendly minor dim; zero-pad the tail (the
    # kernel's mask operand keeps padded lanes out of the statistics)
    minor = 1024 if n >= 1024 else max(n, 1)
    major = -(-n // minor)
    pad = major * minor - n

    def _fold(v, dtype):
        # an already-aligned size needs no tail: skip the no-op concat copy
        if not pad:
            return v.reshape(major, minor)
        return jnp.concatenate(
            [v.reshape(-1), jnp.zeros((pad,), dtype)]).reshape(major, minor)

    x2 = _fold(x, x.dtype)

    if stochastic and not onchip_prng:
        if bits is None:
            if key is None:
                raise ValueError("stochastic path needs `key` or `bits`")
            bits = jax.random.bits(key, shape=(n,), dtype=jnp.uint32)
        bits2 = _fold(bits, jnp.uint32)
    else:
        bits2 = jnp.zeros((major, minor), jnp.uint32)

    seed = jnp.zeros((), jnp.int32)
    if key is not None:
        seed = jax.random.randint(key, (), 0, 2**31 - 1, jnp.int32)
    fmt3 = jnp.stack([fmt.il.astype(jnp.int32), fmt.fl.astype(jnp.int32), seed])

    mask2 = (None if not pad else
             _fold(jnp.ones((n,), jnp.float32), jnp.float32))

    kwargs = dict(stochastic=stochastic, use_onchip_prng=onchip_prng,
                  interpret=interpret)
    if block is not None:
        kwargs["block"] = block
    q2, vec = pallas_fn(x2, fmt3, bits2, mask2, **kwargs)

    q = q2.reshape(-1)[:n].reshape(orig_shape)
    return q, ref_lib.stats_from_vector(vec)


def dps_quantize(x: jax.Array, fmt: FixedPointFormat, *,
                 key: jax.Array | None = None,
                 bits: jax.Array | None = None,
                 stochastic: bool = True,
                 onchip_prng: bool = False,
                 block=None, interpret: bool | None = None):
    """Fused quantize+stats for an arbitrary-rank tensor.

    Returns ``(q, QuantStats)``.  Exactly matches
    ``repro.kernels.ref.dps_quant_ref`` for the bits-operand path.
    """
    return _fold_and_call(dps_quant_pallas, x, fmt, key=key, bits=bits,
                          stochastic=stochastic, onchip_prng=onchip_prng,
                          block=block, interpret=interpret)


def dps_quantize_leaf(x: jax.Array, fmt: FixedPointFormat, *,
                      mode: str = ROUND_STOCHASTIC,
                      key: jax.Array | None = None):
    """One tree leaf's quantize event as one fused kernel: the leaf
    quantizer of ``fixed_point.quantize_tree`` on a TPU.

    Same call and result as ``fixed_point.quantize(x, fmt, mode=mode,
    key=key)``: ``(q in x's dtype, QuantStats)`` for a scalar ``fmt``.
    Leading dims fold into rows and the minor dim stays, so on a TPU the
    kernel reads the leaf's own buffer and its rounding bits come from the
    core's PRNG, seeded from ``key``.  Elsewhere the kernel is interpreted
    with the bits operand ``jax.random.bits(key, x.shape)`` that
    ``quantize`` draws, so q equals ``quantize``'s bit for bit.
    """
    if mode not in (ROUND_STOCHASTIC, ROUND_NEAREST):
        raise ValueError(f"unknown rounding mode {mode!r}")
    stochastic = mode == ROUND_STOCHASTIC
    if stochastic and key is None:
        raise ValueError("stochastic rounding needs `key`")
    chip = on_tpu()
    x2 = x.reshape(-1, x.shape[-1]) if x.ndim else x.reshape(1, 1)
    bits, seed = None, jnp.zeros((), jnp.int32)
    if stochastic and chip:
        seed = jax.lax.bitcast_convert_type(
            jax.random.bits(key, (), jnp.uint32), jnp.int32)
    elif stochastic:
        bits = jax.random.bits(key, x.shape, jnp.uint32).reshape(x2.shape)
    fmt3 = jnp.stack([fmt.il.astype(jnp.int32), fmt.fl.astype(jnp.int32),
                      seed])
    q2, vec = dps_quant_leaf_pallas(x2, fmt3, bits, stochastic=stochastic,
                                    use_onchip_prng=chip, interpret=not chip)
    return q2.reshape(x.shape), ref_lib.stats_from_vector(vec)


def dps_quantize_wire(x: jax.Array, fmt: FixedPointFormat, *,
                      key: jax.Array | None = None,
                      bits: jax.Array | None = None,
                      stochastic: bool = True,
                      onchip_prng: bool = False,
                      block=None, interpret: bool | None = None):
    """Fused quantize → int8 wire payload + stats for an arbitrary-rank
    tensor, in one read-x/write-wire HBM pass.

    Returns ``(wire int8 with x's shape, QuantStats)``.  Exactly matches
    ``repro.kernels.ref.dps_quant_wire_ref`` (and therefore the jnp codec in
    ``repro.dist.collectives``) for the bits-operand path; int8 saturation
    of over-wide formats is counted into ``stats.overflow``.
    """
    return _fold_and_call(dps_quant_wire_pallas, x, fmt, key=key, bits=bits,
                          stochastic=stochastic, onchip_prng=onchip_prng,
                          block=block, interpret=interpret)


def dps_quantize_wire_grouped(x: jax.Array, fmt: FixedPointFormat,
                              tile_group: jax.Array, *,
                              key: jax.Array | None = None,
                              bits: jax.Array | None = None,
                              mask: jax.Array | None = None,
                              stochastic: bool = True,
                              onchip_prng: bool = False,
                              quantum: int = DEFAULT_GROUP_QUANTUM,
                              interpret: bool | None = None,
                              compute_stats: bool = True):
    """Fused per-group wire encode of a group-aligned flat buffer.

    ``x`` is the group-aligned layout (size = ``len(tile_group) ·
    quantum``; see ``repro.dist.collectives.GroupLayout``), ``fmt`` a
    ``[G]``-shaped format whose rows the tiles index via ``tile_group``.
    ``mask`` (1/0 float32, same size) excludes alignment padding from the
    wire and the stats.  Returns ``(wire int8 with x's size,
    [G]-shaped QuantStats)`` in ONE read-x/write-wire HBM pass;
    ``compute_stats=False`` skips the stats accumulation in the kernel
    and returns ``None``.
    """
    if interpret is None:
        interpret = not on_tpu()
    n = x.size
    if stochastic and not onchip_prng:
        if bits is None:
            if key is None:
                raise ValueError("stochastic path needs `key` or `bits`")
            bits = jax.random.bits(key, shape=(n,), dtype=jnp.uint32)
        bits = bits.reshape(-1)
    else:
        bits = jnp.zeros((n,), jnp.uint32)
    seed = jnp.zeros((1,), jnp.int32)
    if key is not None:
        seed = jax.random.randint(key, (1,), 0, 2**31 - 1, jnp.int32)
    if mask is None:
        mask = jnp.ones((n,), jnp.float32)
    fmt_tab = jnp.stack([fmt.il.astype(jnp.int32),
                         fmt.fl.astype(jnp.int32)], axis=1)
    wire, mat = dps_quant_group_wire_pallas(
        x.reshape(-1), fmt_tab, jnp.asarray(tile_group, jnp.int32), seed,
        bits, mask.reshape(-1), stochastic=stochastic,
        use_onchip_prng=onchip_prng, quantum=quantum, interpret=interpret,
        emit_stats=compute_stats)
    return wire, (ref_lib.stats_from_matrix(mat) if compute_stats else None)


def dps_wire_reduce(wire: jax.Array, fmt: FixedPointFormat,
                    tile_group: jax.Array | None = None, *,
                    quantum: int = DEFAULT_GROUP_QUANTUM,
                    interpret: bool | None = None) -> jax.Array:
    """Fused int8 decode → mean over the rank axis (the receive leg).

    ``wire``: ``[n_ranks, chunk]`` int8.  A scalar ``fmt`` decodes every
    tile with one FL (``tile_group`` ignored); a ``[G]`` format needs
    ``tile_group`` (``ceil(chunk / quantum)`` entries) mapping this chunk's
    tiles into the table.  Pads the chunk to a quantum multiple internally
    (zero int8 bytes decode to zero and are sliced back off).  Returns the
    fp32 ``[chunk]`` mean without materializing the decoded ``(n, chunk)``
    fp32 intermediate in HBM.
    """
    if interpret is None:
        interpret = not on_tpu()
    n, chunk = wire.shape
    tiles = -(-chunk // quantum)
    pad = tiles * quantum - chunk
    if pad:
        wire = jnp.pad(wire, ((0, 0), (0, pad)))
    if fmt.il.ndim == 0:
        fmt_tab = jnp.stack([fmt.il, fmt.fl]).astype(jnp.int32)[None, :]
        tile_group = jnp.zeros((tiles,), jnp.int32)
    else:
        if tile_group is None:
            raise ValueError("[G]-shaped formats need a tile_group map")
        fmt_tab = jnp.stack([fmt.il.astype(jnp.int32),
                             fmt.fl.astype(jnp.int32)], axis=1)
        tile_group = jnp.asarray(tile_group, jnp.int32)
        if tile_group.shape[0] != tiles:
            raise ValueError(f"tile_group has {tile_group.shape[0]} entries "
                             f"for {tiles} chunk tiles")
    out = dps_wire_reduce_pallas(wire, fmt_tab, tile_group,
                                 quantum=quantum, interpret=interpret)
    return out[:chunk]
