"""Pallas TPU kernel: fused dynamic fixed-point quantize + statistics.

The paper's per-step hot spot is quantizing *every* weight / activation /
gradient tensor and measuring overflow rate R and quantization error E.
Done naively (as in the paper's Caffe layers) that is four passes over HBM:
read x, write q, read both back for the error reduction.  On TPU we fuse the
whole event into one kernel:

    HBM traffic:  read x (+ random bits on the portable path), write q,
                  plus 7 floats of statistics per grid tile.
    VMEM:         one (block_m, block_n) tile at a time; stats are reduced
                  on-tile to scalars and accumulated into a tiny SMEM-resident
                  accumulator that lives across the grid (dimension_semantics
                  = 'arbitrary' keeps the accumulation race-free).

Two tensor-output flavours share one kernel body:

  * ``dps_quant_pallas`` — emulation: write the dequantized grid value q.
  * ``dps_quant_wire_pallas`` — the collectives' **int8 wire**: write the
    grid integer ``round(q·2^FL)`` saturated at [-128, 127] (saturation
    counts into the overflow stat).  The int8 tile is 4× smaller than the
    input tile, so the wire payload costs one read-x/write-wire pass and
    never exists as an fp32 intermediate in HBM.

The train step's weight and gradient tree passes run a third flavour,
``dps_quant_leaf_pallas``: one launch per tree leaf on the leaf's own
buffer (leading dims folded into rows, the minor dim kept, so the fold is
a bitcast), ragged edges masked in the kernel instead of padded, the
rounding bits from the on-chip PRNG, and the stats accumulated in VMEM
vectors and reduced once.  Its HBM traffic is read x, write q: nothing
else.

Two more kernels give the **per-group** wire pipeline the same one-pass
traffic profile (see ``repro.dist.collectives`` for the layout contract):

  * ``dps_quant_group_wire_pallas`` — the wire variant with a ``[G, 2]``
    ⟨IL, FL⟩ **format table** in SMEM plus a tile→group index map: the
    input is a *group-aligned* flat buffer (every group zero-padded to a
    multiple of the ``quantum`` = one grid tile, so a tile never straddles
    groups), each grid tile resolves its own format out of the table, and
    statistics accumulate into a ``[G, N_STATS]`` VMEM accumulator — G
    per-layer formats in ONE kernel launch, same HBM traffic as the
    global-format wire kernel (read x + bits, write int8 wire).
  * ``dps_wire_reduce_pallas`` — the receive leg: reads the post-all_to_all
    ``(n_ranks, chunk)`` int8 payload and emits the fp32 **mean** chunk
    directly (decode → sum over ranks → ÷n on-tile), so the decoded fp32
    ``(n, chunk)`` intermediate never touches HBM: traffic is n·chunk int8
    in + chunk fp32 out, vs 4·n·chunk fp32 write + (4·n+4)·chunk read for
    the naive decode-then-reduce.

HBM traffic accounting per leg (E = elements, n = ranks):

    naive jnp grouped encode   read 4E (fp32 pad/concat) + write 4E + read
                               4E + write E (int8)     ≈ 13E bytes
    grouped wire kernel        read 4E (+4E bits, portable path) + write E
                                                       ≈ 5E (9E) bytes
    naive decode-reduce        read nE, write 4nE, read 4nE + 4E chunk out
    fused dps_wire_reduce      read nE + write 4E·(1/n per rank)

Bucketed wire (``repro.dist.overlap``): the backward-overlapped schedule
runs the SAME two kernels once per bucket instead of once per tree, so
the per-element traffic is unchanged — but the working set of each
launch shrinks from the whole packed tree to one bucket (default 2^16
elements = 256 KiB fp32 in + 64 KiB int8 out), which fits last-level
cache on the CPU emulation path and one VMEM residency on TPU, and each
bucket's group-aligned layout resolves its own size-aware quantum, so a
bucket of small leaves no longer pays the whole tree's per-group
padding.  ``ops.bucketed_wire_call_geometries`` declares the per-bucket
launch pair statically.

Two variants of the stochastic-rounding noise source:

  * ``use_onchip_prng=False`` (default; CPU-validatable): uniform bits enter
    as a second operand.  Bit-exact against ``ref.dps_quant_ref`` — this is
    what the test sweep asserts.
  * ``use_onchip_prng=True`` (TPU fast path): bits come from the per-core
    hardware PRNG (``pltpu.prng_seed``/``prng_random_bits``), halving HBM
    reads.  The Pallas interpreter cannot execute the PRNG primitive (it
    returns zeros), so off a TPU this path is lowering-validated only; on
    a TPU the train step's tree passes take it (``ops.dps_quantize_leaf``).

⟨IL, FL⟩ arrive as an SMEM scalar-prefetch operand, so precision changes at
every training step re-use the same compiled kernel.

Block shape: (256, 1024) fp32 tiles = 1 MiB in / 1 MiB out — comfortably
inside the ~16 MiB v5e VMEM budget together with the bits operand (1 MiB)
and double buffering (6 MiB total), MXU-aligned (multiples of (8, 128)).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# stats accumulator layout (must match ref.dps_quant_ref)
N_STATS = 7
_IDX_COUNT, _IDX_NZ, _IDX_OVER, _IDX_AERR, _IDX_RERR, _IDX_ASUM, _IDX_MAX = range(7)

DEFAULT_BLOCK = (256, 1024)
_U_BITS = 24
_U_SCALE = 1.0 / (1 << _U_BITS)

# Group-aligned layout quantum: elements covered by one grid tile of the
# grouped kernels.  32×128 is the minimum int8 tile (sublane × lane), so any
# multiple of 4096 lowers cleanly; larger quanta trade per-group padding for
# fewer grid steps (repro.dist.collectives picks the layout).
MIN_GROUP_QUANTUM = 32 * 128
DEFAULT_GROUP_QUANTUM = MIN_GROUP_QUANTUM


def group_block(quantum: int):
    """(bm, bn) tile shape for a grouped-kernel quantum.

    ``quantum`` must be a multiple of 4096 so the int8 wire tile respects
    the (32, 128) minimum; quanta ≥ 32768 widen to 1024 lanes."""
    if quantum % MIN_GROUP_QUANTUM:
        raise ValueError(f"group quantum must be a multiple of "
                         f"{MIN_GROUP_QUANTUM} (32x128 int8 tile), "
                         f"got {quantum}")
    bn = 1024 if quantum % 1024 == 0 and quantum // 1024 >= 32 else 128
    return quantum // bn, bn


# minimum int8 tile (sublane, lane) — every grouped wire tile must be a
# multiple of this shape (see the TPU tiling rules for 1-byte elements)
INT8_MIN_TILE = (32, 128)

# Per-core SMEM budget the scalar-prefetch operands (format table +
# tile→group map + seed) must fit into.  Real v5e SMEM is far larger, but
# the tables are meant to stay tiny — a [G, 2] int32 table with thousands
# of rows signals a mis-built layout, which is exactly what the analyzer
# flags (rule KG-SMEM-TABLE in repro.analysis.kernel_checks).
SMEM_TABLE_BUDGET_BYTES = 64 * 1024


class KernelSignature:
    """Static facts about one Pallas kernel body, declared beside it.

    ``repro.analysis.kernel_checks`` validates call-site geometry against
    these without executing anything — a signature drift (say a new
    scalar-prefetch operand added to the kernel but not its call sites)
    becomes rule KG-PREFETCH-ARITY instead of a Mosaic lowering error
    three layers deep.
    """

    def __init__(self, num_scalar_prefetch: int, scalar_operands: tuple,
                 grouped: bool):
        self.num_scalar_prefetch = num_scalar_prefetch
        self.scalar_operands = scalar_operands
        self.grouped = grouped


# keyed by kernel-body name; scalar_operands lists the SMEM prefetch refs
# in kernel-signature order
KERNEL_SIGNATURES = {
    "_kernel": KernelSignature(
        num_scalar_prefetch=1, scalar_operands=("fmt3[3]",), grouped=False),
    "_group_kernel": KernelSignature(
        num_scalar_prefetch=3,
        scalar_operands=("fmt_tab[G,2]", "tile_group[T]", "seed[1]"),
        grouped=True),
    "_wire_reduce_kernel": KernelSignature(
        num_scalar_prefetch=2,
        scalar_operands=("fmt_tab[G,2]", "tile_group[T]"),
        grouped=True),
    # body lives in repro.kernels.paged_attn (the serving decode step);
    # declared here so kernel_checks sees every kernel in one registry
    "_paged_attn_kernel": KernelSignature(
        num_scalar_prefetch=3,
        scalar_operands=("page_tab[B,P]", "fmt_tab[n_pages,2]",
                         "seq_lens[B]"),
        grouped=True),
}


def _exp2i(n):
    """Bit-exact 2^n inside the kernel (jnp.exp2 is inexact on some
    backends; matches fixed_point.exp2_int).

    ``n`` is a scalar read from SMEM; Mosaic bit-casts vectors only, so the
    exponent is splatted to a (1, 1) vector first.  The result broadcasts
    against any tile like the scalar would."""
    n = jnp.broadcast_to(jnp.clip(n, -126, 127), (1, 1))
    return jax.lax.bitcast_convert_type((n + 127) << 23, jnp.float32)


def _uniform24(bits):
    """Top 24 bits of a uint32 (or int32) tile as a uniform in [0, 1).

    Mosaic has no uint32 -> float32 cast: the bits are reinterpreted as
    int32 and shifted logically, so the 24-bit value is non-negative and
    converts exactly through int32."""
    if bits.dtype != jnp.int32:
        bits = jax.lax.bitcast_convert_type(bits, jnp.int32)
    top = jax.lax.shift_right_logical(bits, jnp.int32(32 - _U_BITS))
    return top.astype(jnp.float32) * _U_SCALE


def _kernel(fmt_ref,            # SMEM: (3,) int32 [il, fl, seed]
            x_ref,              # VMEM: (bm, bn) input tile
            bits_ref,           # VMEM: (bm, bn) uint32 tile (portable path)
            mask_ref,           # VMEM: (bm, bn) float32 1/0 validity tile
            q_ref,              # VMEM out: (bm, bn); int8 wire if emit_wire
            stats_ref,          # SMEM out: (N_STATS,) float32 accumulator
            *, stochastic: bool, use_onchip_prng: bool,
            emit_wire: bool = False):
    i = pl.program_id(0)
    j = pl.program_id(1)

    il = fmt_ref[0]
    fl = fmt_ref[1]
    scale = _exp2i(fl)
    inv_scale = _exp2i(-fl)
    span = _exp2i(il - 1 + fl)
    qmax = span - 1.0
    qmin = -span

    x = x_ref[...].astype(jnp.float32)
    m = mask_ref[...]

    y = x * scale
    over = ((y > qmax) | (y < qmin)).astype(jnp.float32) * m
    yc = jnp.clip(y, qmin, qmax)

    if stochastic:
        if use_onchip_prng:
            # TPU fast path: no bits operand traffic.  Seed is decorrelated
            # per grid tile so every tile draws an independent stream.
            pltpu.prng_seed(fmt_ref[2] + i * pl.num_programs(1) + j)
            bits = pltpu.prng_random_bits(x.shape)
        else:
            bits = bits_ref[...]
        u = _uniform24(bits)
        q_int = jnp.floor(yc + u)
    else:
        q_int = jnp.floor(yc + 0.5)
    q_int = jnp.clip(q_int, qmin, qmax)
    if emit_wire:
        # wire variant: emit int8 grid integers, saturated at int8 capacity.
        # Saturated elements count as overflow (wire clipping IS overflow
        # from the receiver's point of view) and the error is measured
        # against the decoded wire value, matching fixed_point.wire_quantize.
        sat = jnp.clip(q_int, -128.0, 127.0)
        over = (((y > qmax) | (y < qmin) | (q_int != sat))
                .astype(jnp.float32) * m)
        q_ref[...] = (sat * m).astype(q_ref.dtype)
        q = sat * inv_scale
    else:
        q = q_int * inv_scale
        q_ref[...] = (q * m).astype(q_ref.dtype)

    # --- on-tile stats reduction (rounding error vs clipped reference) ---
    x_ref_val = yc * inv_scale
    abs_err = jnp.abs(q - x_ref_val) * m
    abs_ref = jnp.abs(x_ref_val) * m
    nz = (abs_ref > 0.0).astype(jnp.float32)
    rel = jnp.where(abs_ref > 0.0, abs_err / jnp.where(abs_ref > 0.0, abs_ref, 1.0), 0.0)

    @pl.when((i == 0) & (j == 0))
    def _init():
        for k in range(N_STATS):
            stats_ref[k] = 0.0

    stats_ref[_IDX_COUNT] += jnp.sum(m)
    stats_ref[_IDX_NZ] += jnp.sum(nz)
    stats_ref[_IDX_OVER] += jnp.sum(over)
    stats_ref[_IDX_AERR] += jnp.sum(abs_err)
    stats_ref[_IDX_RERR] += jnp.sum(rel)
    stats_ref[_IDX_ASUM] += jnp.sum(abs_ref)
    stats_ref[_IDX_MAX] = jnp.maximum(stats_ref[_IDX_MAX], jnp.max(jnp.abs(x) * m))


def _pallas_quant(x: jax.Array, fmt3: jax.Array, bits: jax.Array,
                  mask: jax.Array | None,
                  *, stochastic: bool, use_onchip_prng: bool,
                  block, interpret: bool, emit_wire: bool):
    M, N = x.shape
    bm = min(block[0], M) if M % block[0] else block[0]
    bn = min(block[1], N) if N % block[1] else block[1]
    # pad to the tile grid; mask marks the valid region.  When the shape is
    # already tile-aligned the pads would be no-ops that still cost an HBM
    # copy each (x, bits, mask) — skip them.
    Mp = pl.cdiv(M, bm) * bm
    Np = pl.cdiv(N, bn) * bn
    if (Mp, Np) == (M, N):
        xp, bp = x, bits
        if mask is None:
            mask = jnp.ones((M, N), jnp.float32)
    else:
        xp = jnp.pad(x, ((0, Mp - M), (0, Np - N)))
        bp = jnp.pad(bits, ((0, Mp - M), (0, Np - N)))
        if mask is None:
            mask = jnp.pad(jnp.ones((M, N), jnp.float32),
                           ((0, Mp - M), (0, Np - N)))
        else:
            mask = jnp.pad(mask, ((0, Mp - M), (0, Np - N)))

    grid = (Mp // bm, Np // bn)
    out_dtype = jnp.int8 if emit_wire else x.dtype
    kernel = functools.partial(_kernel, stochastic=stochastic,
                               use_onchip_prng=use_onchip_prng,
                               emit_wire=emit_wire)
    q, stats = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                # index maps receive the scalar-prefetch refs as trailing args
                pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j)),
                pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j)),
                pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j)),
            ],
            out_specs=[
                pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j)),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Mp, Np), out_dtype),
            jax.ShapeDtypeStruct((N_STATS,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(fmt3, xp, bp, mask)
    return q[:M, :N], stats


@functools.partial(jax.jit, static_argnames=("stochastic", "use_onchip_prng",
                                             "block", "interpret"))
def dps_quant_pallas(x: jax.Array, fmt3: jax.Array, bits: jax.Array,
                     mask: jax.Array | None = None,
                     *, stochastic: bool = True, use_onchip_prng: bool = False,
                     block=DEFAULT_BLOCK, interpret: bool = False):
    """Run the fused kernel on a 2-D fp32/bf16 array.

    ``fmt3`` = int32[3] = [il, fl, seed].  ``bits`` uint32, same shape as x
    (ignored when ``use_onchip_prng``).  ``mask`` (float32 1/0, same shape)
    marks elements that belong in the statistics; grid padding added here is
    masked automatically.  Returns ``(q, stats_vec[7])``.
    """
    return _pallas_quant(x, fmt3, bits, mask, stochastic=stochastic,
                         use_onchip_prng=use_onchip_prng, block=block,
                         interpret=interpret, emit_wire=False)


@functools.partial(jax.jit, static_argnames=("stochastic", "use_onchip_prng",
                                             "block", "interpret"))
def dps_quant_wire_pallas(x: jax.Array, fmt3: jax.Array, bits: jax.Array,
                          mask: jax.Array | None = None,
                          *, stochastic: bool = True,
                          use_onchip_prng: bool = False,
                          block=DEFAULT_BLOCK, interpret: bool = False):
    """Fused quantize → **int8 wire** + stats in one read-x/write-wire pass.

    Same contract as :func:`dps_quant_pallas` except the tensor output is
    the int8 grid-integer wire payload (what the collectives ship), with
    int8 saturation folded into the overflow count.  Bit-exact against
    ``ref.dps_quant_wire_ref`` on the portable (bits-operand) path.  The
    int8 tile is 4× smaller than the fp32 input tile, so HBM traffic is
    read-x + write-wire (+ bits on the portable path) — the wire payload
    never exists as an fp32 intermediate in HBM.
    """
    return _pallas_quant(x, fmt3, bits, mask, stochastic=stochastic,
                         use_onchip_prng=use_onchip_prng, block=block,
                         interpret=interpret, emit_wire=True)


# ---------------------------------------------------------------------------
# Tree-leaf kernel: the weight and gradient snaps of the train step.
# ---------------------------------------------------------------------------

# HBM bytes per grid block and lanes per block of the leaf kernel: 4 MiB,
# a 1024 x 1024 block of bf16 in and out, double-buffered 8 MiB of VMEM.
# Measured on a v5e at the benchmark's bf16 leaves, blocks of 2^18
# elements ran at ~510 GB/s and of 2^20 at ~610, beside 630 GB/s for a
# plain copy.  The body works through a block in unrolled strips of 32
# rows, so its f32 temporaries stay a few dozen vregs whatever the block
# (at 2^18-element blocks: unrolled strips ~510 GB/s, a fori_loop over
# them ~370, the math on the whole block at once ~470).
LEAF_BLOCK_BYTES = 4 << 20
LEAF_LANES = 1024
# block rows are a multiple of this: the int8 tile's 32 sublanes, which
# also covers the f32 (8) and bf16 (16) tiles
_LEAF_ROW_UNIT = 32


def _leaf_dim(n: int, unit: int, cap: int) -> int:
    """Block length along a dim of ``n``: all of it when ``n < unit``,
    else a multiple of ``unit`` of at most ``cap`` that divides ``n``,
    where one of at least ``cap / 8`` does, else the largest multiple (the
    grid's last block then hangs past the edge, masked in the kernel)."""
    if n < unit:
        return n
    top = min(cap, n) // unit * unit
    for b in range(top, max(unit, top // 8) - 1, -unit):
        if n % b == 0:
            return b
    return top


def _leaf_block(rows: int, cols: int, elem_bytes: int):
    """(bm, bn) grid block of the leaf kernel on a ``[rows, cols]`` leaf
    whose elements move ``elem_bytes`` bytes each (x in, q out, and the
    bits on the portable path)."""
    bn = _leaf_dim(cols, 128, LEAF_LANES)
    cap = max(_LEAF_ROW_UNIT, LEAF_BLOCK_BYTES // elem_bytes // bn
              // _LEAF_ROW_UNIT * _LEAF_ROW_UNIT)
    return _leaf_dim(rows, _LEAF_ROW_UNIT, cap), bn


def _leaf_kernel(fmt_ref,        # SMEM: (3,) int32 [il, fl, seed]
                 x_ref,          # VMEM: (bm, bn) block of the leaf
                 *refs,          # [bits (bm, bn) uint32,] q out, stats
                                 # out (N_STATS,) SMEM, acc scratch
                 stochastic: bool, use_onchip_prng: bool, shape,
                 ragged: bool, strip: int):
    if stochastic and not use_onchip_prng:
        bits_ref, q_ref, stats_ref, acc_ref = refs
    else:
        bits_ref = None
        q_ref, stats_ref, acc_ref = refs
    i, j = pl.program_id(0), pl.program_id(1)
    last = ((i == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
    bm, bn = x_ref.shape
    scale = _exp2i(fmt_ref[1])
    inv_scale = _exp2i(-fmt_ref[1])
    span = _exp2i(fmt_ref[0] - 1 + fmt_ref[1])
    qmax = span - 1.0
    qmin = -span

    @pl.when((i == 0) & (j == 0))
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if stochastic and use_onchip_prng:
        # one stream per grid block, as in _kernel
        pltpu.prng_seed(fmt_ref[2] + i * pl.num_programs(1) + j)

    def body(r, acc):
        x = x_ref[pl.ds(r, strip), :].astype(jnp.float32)
        if ragged:
            # lanes past the leaf's edge read as zero, which no stat counts
            # (count is the leaf's static size)
            rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
            ok = ((rows < shape[0] - i * bm - r)
                  & (cols < shape[1] - j * bn))
            x = jnp.where(ok, x, 0.0)
        y = x * scale
        yc = jnp.clip(y, qmin, qmax)
        if stochastic:
            bits = (pltpu.prng_random_bits(x.shape) if use_onchip_prng
                    else bits_ref[pl.ds(r, strip), :])
            q_int = jnp.floor(yc + _uniform24(bits))
        else:
            q_int = jnp.floor(yc + 0.5)
        # floor(yc + u) >= qmin already; only f32 rounding of yc + u can
        # pass qmax
        q_int = jnp.minimum(q_int, qmax)
        q_ref[pl.ds(r, strip), :] = (q_int * inv_scale).astype(q_ref.dtype)
        # The stats in grid units: scaling by 2^-FL is exact, so the error
        # and magnitude sums scale once at the end, and the relative error
        # needs no scale at all.  yc == 0 gives q_int == 0, so the zero
        # lanes add 0 / 1.
        err = jnp.abs(q_int - yc)
        mag = jnp.abs(yc)
        nz = mag > 0.0
        # err / mag: the EUP's reciprocal and one Newton step, ~1 ulp
        den = jnp.where(nz, mag, 1.0)
        inv = pl.reciprocal(den, approx=True)
        inv = inv * (2.0 - den * inv)
        parts = (jnp.where(nz, 1.0, 0.0), jnp.where(y != yc, 1.0, 0.0),
                 err, err * inv, mag)
        return (tuple(a + p for a, p in zip(acc, parts))
                + (jnp.maximum(acc[5], jnp.abs(x)),))

    acc = tuple(acc_ref[k] for k in range(N_STATS - 1))
    for r in range(0, bm, strip):
        acc = body(r, acc)
    for k in range(N_STATS - 1):
        acc_ref[k] = acc[k]

    @pl.when(last)
    def _finish():
        stats_ref[_IDX_COUNT] = jnp.float32(shape[0] * shape[1])
        stats_ref[_IDX_NZ] = jnp.sum(acc_ref[0])
        stats_ref[_IDX_OVER] = jnp.sum(acc_ref[1])
        stats_ref[_IDX_AERR] = jnp.sum(acc_ref[2]) * inv_scale[0, 0]
        stats_ref[_IDX_RERR] = jnp.sum(acc_ref[3])
        stats_ref[_IDX_ASUM] = jnp.sum(acc_ref[4]) * inv_scale[0, 0]
        stats_ref[_IDX_MAX] = jnp.max(acc_ref[N_STATS - 2])


@functools.partial(jax.jit, static_argnames=("stochastic", "use_onchip_prng",
                                             "interpret"))
def dps_quant_leaf_pallas(x: jax.Array, fmt3: jax.Array,
                          bits: jax.Array | None = None,
                          *, stochastic: bool = True,
                          use_onchip_prng: bool = False,
                          interpret: bool = False):
    """Fused quantize + stats of one ``[rows, cols]`` tree leaf, in place
    of its shape: read x, write q in x's dtype, nothing else.

    ``fmt3`` = int32[3] = [il, fl, seed].  ``bits`` (uint32, x's shape) is
    the stochastic rounding's noise on the portable path, and must be
    ``None`` under ``use_onchip_prng`` or nearest rounding.  No pad, mask
    or relayout of x: blocks keep the leaf's minor dim (``_leaf_block``),
    the last block of a ragged dim hangs past the edge and the kernel
    masks it.  Returns ``(q, stats_vec[7])``, ``stats_vec`` laid out as
    :func:`dps_quant_pallas`'s.  On the bits path q is bit-exact against
    ``ref.dps_quant_ref`` and the stats agree to f32 summation order (the
    relative error's reciprocal to ~1 ulp) for finite x; a NaN counts as
    overflow here.
    """
    if (bits is not None) != (stochastic and not use_onchip_prng):
        raise ValueError("bits go with stochastic rounding off the on-chip "
                         "PRNG, and only there")
    rows, cols = x.shape
    bm, bn = _leaf_block(rows, cols, 2 * x.dtype.itemsize
                        + (0 if bits is None else 4))
    strip = _LEAF_ROW_UNIT if bm % _LEAF_ROW_UNIT == 0 else bm
    kernel = functools.partial(
        _leaf_kernel, stochastic=stochastic, use_onchip_prng=use_onchip_prng,
        shape=(rows, cols), ragged=bool(rows % bm or cols % bn), strip=strip)
    block = pl.BlockSpec((bm, bn), lambda i, j, *_: (i, j))
    operands = (x,) if bits is None else (x, bits)
    q, stats = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(rows, bm), pl.cdiv(cols, bn)),
            in_specs=[block] * len(operands),
            out_specs=[block, pl.BlockSpec(memory_space=pltpu.SMEM)],
            scratch_shapes=[pltpu.VMEM((N_STATS - 1, strip, bn),
                                       jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((rows, cols), x.dtype),
                   jax.ShapeDtypeStruct((N_STATS,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="dps_quant",
    )(fmt3, *operands)
    return q, stats


# ---------------------------------------------------------------------------
# Grouped wire kernel: [G, 2] SMEM format table, one format per grid tile.
# ---------------------------------------------------------------------------

def _group_kernel(fmt_ref,           # SMEM: (G, 2) int32 [[il, fl], ...]
                  tgrp_ref,          # SMEM: (T,) int32 tile -> group index
                  seed_ref,          # SMEM: (1,) int32 PRNG seed
                  x_ref,             # VMEM: (bm, bn) input tile
                  bits_ref,          # VMEM: (bm, bn) uint32 (portable path)
                  mask_ref,          # VMEM: (bm, bn) float32 validity
                  wire_ref,          # VMEM out: (bm, bn) int8 grid integers
                  stats_ref=None,    # VMEM out: (G, N_STATS); None when the
                                     # caller asked for wire only
                  *, stochastic: bool, use_onchip_prng: bool):
    t = pl.program_id(0)
    g = tgrp_ref[t]
    il = fmt_ref[g, 0]
    fl = fmt_ref[g, 1]

    scale = _exp2i(fl)
    inv_scale = _exp2i(-fl)
    span = _exp2i(il - 1 + fl)
    qmax = span - 1.0
    qmin = -span

    x = x_ref[...].astype(jnp.float32)
    m = mask_ref[...]

    y = x * scale
    yc = jnp.clip(y, qmin, qmax)
    if stochastic:
        if use_onchip_prng:
            pltpu.prng_seed(seed_ref[0] + t)
            bits = pltpu.prng_random_bits(x.shape)
        else:
            bits = bits_ref[...]
        u = _uniform24(bits)
        q_int = jnp.floor(yc + u)
    else:
        q_int = jnp.floor(yc + 0.5)
    q_int = jnp.clip(q_int, qmin, qmax)
    sat = jnp.clip(q_int, -128.0, 127.0)
    over = (((y > qmax) | (y < qmin) | (q_int != sat))
            .astype(jnp.float32) * m)
    wire_ref[...] = (sat * m).astype(wire_ref.dtype)
    if stats_ref is None:        # wire-only launch (e.g. the receive-side
        return                   # re-encode leg, whose stats nobody reads)
    q = sat * inv_scale

    # --- on-tile stats, accumulated into this tile's group row ---
    x_ref_val = yc * inv_scale
    abs_err = jnp.abs(q - x_ref_val) * m
    abs_ref = jnp.abs(x_ref_val) * m
    nz = (abs_ref > 0.0).astype(jnp.float32)
    rel = jnp.where(abs_ref > 0.0,
                    abs_err / jnp.where(abs_ref > 0.0, abs_ref, 1.0), 0.0)

    @pl.when(t == 0)
    def _init():
        stats_ref[...] = jnp.zeros_like(stats_ref)

    zero = jnp.float32(0)
    row_add = jnp.stack([jnp.sum(m), jnp.sum(nz), jnp.sum(over),
                         jnp.sum(abs_err), jnp.sum(rel), jnp.sum(abs_ref),
                         zero])                       # (N_STATS,), max col 0
    row_max = jnp.stack([zero] * (N_STATS - 1)
                        + [jnp.max(jnp.abs(x) * m)])  # max col only
    G = stats_ref.shape[0]
    onehot = (jax.lax.broadcasted_iota(jnp.int32, (G, 1), 0) == g
              ).astype(jnp.float32)
    cur = stats_ref[...]
    # every stat is >= 0, so one fused update covers both combine rules:
    # sums add their (one-hot-masked) row, the max column maxes against it.
    stats_ref[...] = jnp.maximum(cur + onehot * row_add[None, :],
                                 onehot * row_max[None, :])


@functools.partial(jax.jit, static_argnames=("stochastic", "use_onchip_prng",
                                             "quantum", "interpret",
                                             "emit_stats"))
def dps_quant_group_wire_pallas(x: jax.Array, fmt_tab: jax.Array,
                                tile_group: jax.Array, seed: jax.Array,
                                bits: jax.Array, mask: jax.Array,
                                *, stochastic: bool = True,
                                use_onchip_prng: bool = False,
                                quantum: int = DEFAULT_GROUP_QUANTUM,
                                interpret: bool = False,
                                emit_stats: bool = True):
    """Per-group ⟨IL, FL⟩ wire encode of a group-aligned flat buffer.

    ``x``: flat fp32/bf16 buffer whose size is ``T · quantum`` — the
    group-aligned layout (each group padded to a quantum multiple, so a
    tile never straddles groups; ``mask`` zeroes the padding out of both
    the wire and the statistics).  ``fmt_tab``: int32 ``[G, 2]`` rows of
    ``[IL, FL]`` — the SMEM-prefetched format table.  ``tile_group``:
    int32 ``[T]`` mapping grid tile → table row.  ``bits``/``mask``: same
    size as ``x`` (bits ignored under ``use_onchip_prng``); ``seed``:
    int32 ``[1]`` for the on-chip PRNG.

    Returns ``(wire int8 [T·quantum], stats float32 [G, N_STATS])`` —
    bit-exact against ``ref.dps_quant_group_wire_ref`` on the portable
    path, and against G independent ``dps_quant_wire_pallas`` calls on the
    per-group slices.  One read-x/write-wire HBM pass for all G formats.
    ``emit_stats=False`` drops the accumulator entirely (no per-tile stat
    reductions, no [G, N_STATS] output; stats come back ``None``) — the
    receive-side re-encode leg runs wire-only.
    """
    n = x.size
    if n % quantum:
        raise ValueError(f"group-aligned buffer size {n} is not a multiple "
                         f"of the quantum {quantum}")
    bm, bn = group_block(quantum)
    tiles = n // quantum
    x2 = x.reshape(tiles * bm, bn)
    b2 = bits.reshape(tiles * bm, bn)
    m2 = mask.reshape(tiles * bm, bn)
    G = fmt_tab.shape[0]
    kernel = functools.partial(_group_kernel, stochastic=stochastic,
                               use_onchip_prng=use_onchip_prng)
    out_specs = [pl.BlockSpec((bm, bn), lambda t, *_: (t, 0))]
    out_shape = [jax.ShapeDtypeStruct((tiles * bm, bn), jnp.int8)]
    if emit_stats:
        # the [G, N_STATS] accumulator revisits one block across the
        # whole grid ('arbitrary' semantics keep it race-free)
        out_specs.append(pl.BlockSpec((G, N_STATS), lambda t, *_: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((G, N_STATS), jnp.float32))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec((bm, bn), lambda t, *_: (t, 0)),
                pl.BlockSpec((bm, bn), lambda t, *_: (t, 0)),
                pl.BlockSpec((bm, bn), lambda t, *_: (t, 0)),
            ],
            out_specs=out_specs,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(fmt_tab, tile_group, seed, x2, b2, m2)
    wire = out[0].reshape(n)
    return wire, (out[1] if emit_stats else None)


# ---------------------------------------------------------------------------
# Fused int8 decode-reduce: (n_ranks, chunk) wire -> fp32 mean chunk.
# ---------------------------------------------------------------------------

def _wire_reduce_kernel(fmt_ref,     # SMEM: (G, 2) int32 format table
                        tgrp_ref,    # SMEM: (T,) int32 tile -> group
                        w_ref,       # VMEM: (n, bm, bn) int8 wire stack
                        out_ref):    # VMEM out: (bm, bn) fp32 mean tile
    t = pl.program_id(0)
    g = tgrp_ref[t]
    inv_scale = _exp2i(-fmt_ref[g, 1])
    n = w_ref.shape[0]
    dec = w_ref[...].astype(jnp.float32) * inv_scale
    # every decoded value is a multiple of 2^-FL with |w| <= 127, so the
    # fp32 sum is exact for any practical rank count (n·127 < 2^24) and the
    # single ÷n rounds identically to the jnp decode-then-mean path.
    out_ref[...] = jnp.sum(dec, axis=0) / jnp.float32(n)


@functools.partial(jax.jit, static_argnames=("quantum", "interpret"))
def dps_wire_reduce_pallas(wire: jax.Array, fmt_tab: jax.Array,
                           tile_group: jax.Array,
                           *, quantum: int = DEFAULT_GROUP_QUANTUM,
                           interpret: bool = False):
    """Fused decode → sum → mean over the rank axis of an int8 payload.

    ``wire``: int8 ``[n_ranks, chunk]`` (chunk a quantum multiple) — the
    post-``all_to_all`` stack where row i is rank i's contribution to this
    rank's chunk.  ``fmt_tab``/``tile_group``: as in
    :func:`dps_quant_group_wire_pallas`, indexed by this chunk's tiles (a
    global format is the G=1 table).  Returns the fp32 ``[chunk]`` mean —
    the decoded ``(n, chunk)`` fp32 intermediate never exists in HBM.
    """
    n, chunk = wire.shape
    if chunk % quantum:
        raise ValueError(f"chunk {chunk} is not a multiple of the "
                         f"quantum {quantum}")
    bm, bn = group_block(quantum)
    tiles = chunk // quantum
    w3 = wire.reshape(n, tiles * bm, bn)
    out = pl.pallas_call(
        _wire_reduce_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec((n, bm, bn), lambda t, *_: (0, t, 0)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda t, *_: (t, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((tiles * bm, bn), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(fmt_tab, tile_group, w3)
    return out.reshape(chunk)
