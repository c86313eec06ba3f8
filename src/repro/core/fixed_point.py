"""Dynamic fixed-point ⟨IL, FL⟩ emulation with fused quantization statistics.

This is the paper's numerical substrate (§2.1).  A fixed-point format is a
pair of bit-widths ``⟨IL, FL⟩``: IL integer bits (including sign) and FL
fractional bits.  The representable grid is ``k · 2^-FL`` for integers
``k ∈ [-2^(IL-1+FL), 2^(IL-1+FL) - 1]``.

Key property for a *dynamic* precision scheme inside ``jit``: IL and FL are
**traced int32 scalars**, never Python ints, so the controller can change
them every training step without triggering recompilation.  All scale factors
are derived with ``exp2`` on traced values.

Exactness: emulation math runs in float32.  Grid integers are exact in
float32 iff ``IL - 1 + FL <= 24`` (fp32 mantissa); controllers clamp widths
to honour this, and tests assert bit-exactness in that regime.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

# Fraction-of-a-unit resolution used for stochastic rounding: uniform samples
# are exact multiples of 2^-24, matching fp32 mantissa resolution.
_U_BITS = 24
_U_SCALE = 1.0 / (1 << _U_BITS)

ROUND_NEAREST = "nearest"
ROUND_STOCHASTIC = "stochastic"


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FixedPointFormat:
    """A (possibly batched) dynamic fixed-point format.

    ``il``/``fl`` are int32 arrays (scalars for global granularity, shape
    ``[G]`` for per-group granularity).  They are pytree leaves: traced under
    ``jit``, checkpointable, donate-able.
    """

    il: jax.Array
    fl: jax.Array

    @staticmethod
    def create(il: int, fl: int) -> "FixedPointFormat":
        return FixedPointFormat(jnp.asarray(il, jnp.int32), jnp.asarray(fl, jnp.int32))

    def total_bits(self) -> jax.Array:
        return self.il + self.fl


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QuantStats:
    """Sufficient statistics of one quantization event.

    All fields are sums/counts (or max for ``max_abs``) so they combine
    across tensors, layers, and mesh shards (``psum`` for sums, ``pmax`` for
    the max) without bias.
    """

    count: jax.Array          # f32, number of elements
    nonzero: jax.Array        # f32, elements with |x| > 0 (for relative mean)
    overflow: jax.Array       # f32, elements clipped at the range boundary
    abs_err_sum: jax.Array    # f32, Σ |q - clip(x)| (rounding error only)
    rel_err_sum: jax.Array    # f32, Σ |q - clip(x)| / |clip(x)| over nonzero
    abs_sum: jax.Array        # f32, Σ |clip(x)|
    max_abs: jax.Array        # f32, max |x| (pre-clip; FlexPoint-style predictor)

    @staticmethod
    def zero(shape=()) -> "QuantStats":
        z = jnp.zeros(shape, jnp.float32)
        return QuantStats(z, z, z, z, z, z, z)

    def merge(self, other: "QuantStats") -> "QuantStats":
        return QuantStats(
            self.count + other.count,
            self.nonzero + other.nonzero,
            self.overflow + other.overflow,
            self.abs_err_sum + other.abs_err_sum,
            self.rel_err_sum + other.rel_err_sum,
            self.abs_sum + other.abs_sum,
            jnp.maximum(self.max_abs, other.max_abs),
        )

    # --- derived metrics (paper §2.2) ---
    def overflow_rate(self) -> jax.Array:
        """R: fraction of values that clipped — drives IL."""
        return self.overflow / jnp.maximum(self.count, 1.0)

    def quant_error(self, metric: str = "relative_mean") -> jax.Array:
        """E: average quantization error percentage — drives FL.

        ``relative_mean``: mean over nonzero elements of |q-x|/|x| (the
        paper's "average quantization error percentage"; saturates at 1.0 for
        round-to-zero events, which the paper identifies as the FL driver).
        ``ratio``: Σ|q-x| / Σ|x| (aggregate alternative, less sensitive to
        tiny-magnitude elements).
        """
        if metric == "relative_mean":
            return self.rel_err_sum / jnp.maximum(self.nonzero, 1.0)
        elif metric == "ratio":
            return self.abs_err_sum / jnp.maximum(self.abs_sum, 1e-30)
        raise ValueError(f"unknown error metric {metric!r}")


def merge_stats(*stats: QuantStats) -> QuantStats:
    out = stats[0]
    for s in stats[1:]:
        out = out.merge(s)
    return out


def exp2_int(n: jax.Array) -> jax.Array:
    """Bit-exact ``2.0 ** n`` for int32 ``n`` in [-126, 127].

    ``jnp.exp2`` is NOT bit-exact on all backends (this container's CPU
    backend returns ``exp2(13.0) == 8192.0039``), which would knock every
    quantized value off the ⟨IL, FL⟩ grid.  Constructing the float32 from
    its exponent bits is exact by definition.
    """
    n = jnp.clip(jnp.asarray(n, jnp.int32), -126, 127)
    return jax.lax.bitcast_convert_type((n + 127) << 23, jnp.float32)


def grid_bounds(fmt: FixedPointFormat):
    """Scale factors and integer-grid bounds for a format (traced-safe)."""
    scale = exp2_int(fmt.fl)             # x -> grid units
    inv_scale = exp2_int(-fmt.fl)        # grid units -> x
    span = exp2_int(fmt.il - 1 + fmt.fl)
    qmax = span - 1.0                    # largest grid integer
    qmin = -span                         # smallest grid integer
    return scale, inv_scale, qmin, qmax


def _uniform_from_bits(bits: jax.Array) -> jax.Array:
    """uint32 random bits -> exact fp32 uniforms in [0, 1) at 2^-24 grid."""
    return (bits >> (32 - _U_BITS)).astype(jnp.float32) * _U_SCALE


def _grid_round(x: jax.Array, fmt_b: FixedPointFormat, mode: str,
                bits: Optional[jax.Array], key: Optional[jax.Array]):
    """Shared grid-rounding core of :func:`quantize` / :func:`wire_quantize`.

    Returns ``(xf, over_range, yc, q_int, inv_scale)`` where ``q_int`` is
    the rounded grid integer clipped to the ⟨IL, FL⟩ range and ``yc`` the
    range-clipped value in grid units.  One implementation of the paper's
    Eq. (1)/(2) keeps the emulation and the wire codec bit-identical.
    """
    xf = x.astype(jnp.float32)
    scale, inv_scale, qmin, qmax = grid_bounds(fmt_b)

    y = xf * scale
    over_range = (y > qmax) | (y < qmin)
    yc = jnp.clip(y, qmin, qmax)

    if mode == ROUND_STOCHASTIC:
        if bits is None:
            if key is None:
                raise ValueError("stochastic rounding needs `bits` or `key`")
            bits = jax.random.bits(key, shape=x.shape, dtype=jnp.uint32)
        u = _uniform_from_bits(bits)
        q_int = jnp.floor(yc + u)
    elif mode == ROUND_NEAREST:
        q_int = jnp.floor(yc + 0.5)
    else:
        raise ValueError(f"unknown rounding mode {mode!r}")
    # floor(qmax + u) can exceed qmax when u -> 1 only if yc == qmax exactly
    # and u == 1 (excluded); the extra clip guards fp edge cases for free.
    q_int = jnp.clip(q_int, qmin, qmax)
    return xf, over_range, yc, q_int, inv_scale


def quantize(
    x: jax.Array,
    fmt: FixedPointFormat,
    *,
    mode: str = ROUND_STOCHASTIC,
    bits: Optional[jax.Array] = None,
    key: Optional[jax.Array] = None,
    compute_stats: bool = True,
):
    """Quantize ``x`` onto the ⟨IL, FL⟩ grid.  Returns ``(q, stats | None)``.

    ``mode='stochastic'`` implements the paper's Eq. (2): unbiased rounding,
    E[q] = clip(x).  Supply either ``bits`` (uint32, same shape as x — the
    deterministic, kernel-matching path) or ``key`` (bits drawn internally).
    ``mode='nearest'`` implements Eq. (1) (round half away from floor, i.e.
    floor(y + 0.5)).

    The returned ``q`` has x's dtype; internal math is fp32.  Stats measure
    *rounding* error against the range-clipped reference (overflow is
    reported separately via the overflow count, mirroring Alg. 2's split of
    responsibilities: R -> IL, E -> FL).
    """
    orig_dtype = x.dtype
    xf, over, yc, q_int, inv_scale = _grid_round(x, fmt, mode, bits, key)
    q = q_int * inv_scale

    stats = None
    if compute_stats:
        x_ref = yc * inv_scale           # range-clipped reference value
        abs_err = jnp.abs(q - x_ref)
        abs_ref = jnp.abs(x_ref)
        nz = abs_ref > 0.0
        rel = jnp.where(nz, abs_err / jnp.where(nz, abs_ref, 1.0), 0.0)
        stats = QuantStats(
            count=jnp.asarray(x.size, jnp.float32),
            nonzero=jnp.sum(nz.astype(jnp.float32)),
            overflow=jnp.sum(over.astype(jnp.float32)),
            abs_err_sum=jnp.sum(abs_err),
            rel_err_sum=jnp.sum(rel),
            abs_sum=jnp.sum(abs_ref),
            max_abs=jnp.max(jnp.abs(xf)) if x.size else jnp.float32(0),
        )
    return q.astype(orig_dtype), stats


# Capacity of the int8 wire payload used by repro.dist.collectives: grid
# integers outside [-128, 127] saturate (and are counted as overflow).
WIRE_QMIN = -128.0
WIRE_QMAX = 127.0


def wire_quantize(
    x: jax.Array,
    fmt: FixedPointFormat,
    *,
    mode: str = ROUND_STOCHASTIC,
    bits: Optional[jax.Array] = None,
    key: Optional[jax.Array] = None,
    compute_stats: bool = True,
    mask: Optional[jax.Array] = None,
):
    """Quantize ``x`` onto the ⟨IL, FL⟩ grid and emit int8 *grid integers*.

    The wire payload is ``round(q · 2^FL)`` saturated at int8 capacity
    ``[-128, 127]``.  For IL + FL ≤ 8 the grid fits the wire exactly and
    the result is bit-identical to :func:`quantize` followed by the
    integer conversion; for over-wide formats the saturated elements are
    counted into ``stats.overflow`` and the reported rounding error is
    measured against the *decoded wire value*, so a controller consuming
    these stats sees wire clipping as what it is — overflow.

    Per-group formats: when ``fmt.il``/``fmt.fl`` have shape ``[G]`` (or
    any non-scalar shape), the leading ``fmt.il.ndim`` dims of ``x`` must
    equal ``fmt.il.shape``; stats reduce over the remaining trailing dims,
    so every stats leaf comes out with shape ``fmt.il.shape``.

    ``mask`` (same shape as x, 1/0) excludes padding from the statistics
    and zeroes the corresponding wire bytes.

    Returns ``(wire int8 with x's shape, stats | None)``.
    """
    nd = fmt.il.ndim
    if x.ndim < nd or x.shape[:nd] != fmt.il.shape:
        raise ValueError(
            f"per-group format {fmt.il.shape} needs x leading dims to match, "
            f"got x shape {x.shape}")
    bshape = fmt.il.shape + (1,) * (x.ndim - nd)
    fmt_b = FixedPointFormat(fmt.il.reshape(bshape), fmt.fl.reshape(bshape))
    axes = tuple(range(nd, x.ndim))

    m = jnp.ones(x.shape, jnp.float32) if mask is None else mask.astype(jnp.float32)
    xf, over_range, yc, q_int, inv_scale = _grid_round(x, fmt_b, mode, bits, key)
    sat = jnp.clip(q_int, WIRE_QMIN, WIRE_QMAX)
    wire = (sat * m).astype(jnp.int8)

    stats = None
    if compute_stats:
        over = ((over_range | (q_int != sat)).astype(jnp.float32)) * m
        x_ref = yc * inv_scale              # range-clipped reference value
        dec = sat * inv_scale               # what the receiver will decode
        abs_err = jnp.abs(dec - x_ref) * m
        abs_ref = jnp.abs(x_ref) * m
        nz = (abs_ref > 0.0).astype(jnp.float32)
        rel = jnp.where(abs_ref > 0.0,
                        abs_err / jnp.where(abs_ref > 0.0, abs_ref, 1.0), 0.0)
        stats = QuantStats(
            count=jnp.sum(m, axis=axes),
            nonzero=jnp.sum(nz, axis=axes),
            overflow=jnp.sum(over, axis=axes),
            abs_err_sum=jnp.sum(abs_err, axis=axes),
            rel_err_sum=jnp.sum(rel, axis=axes),
            abs_sum=jnp.sum(abs_ref, axis=axes),
            max_abs=(jnp.max(jnp.abs(xf) * m, axis=axes) if x.size
                     else jnp.zeros(fmt.il.shape, jnp.float32)),
        )
    return wire, stats


def quantize_tree(tree, fmt: FixedPointFormat, *, mode: str = ROUND_STOCHASTIC,
                  key: Optional[jax.Array] = None, predicate=None,
                  quantize_fn=quantize):
    """Quantize every leaf of a pytree with one shared format.

    ``predicate(path, leaf) -> bool`` selects which leaves are quantized
    (see ``repro.core.policy``).  Returns ``(tree_q, merged QuantStats)``.
    Per-leaf RNG derives from ``key`` by leaf index (stable ordering).
    ``quantize_fn(x, fmt, mode=, key=) -> (q, QuantStats)`` quantizes one
    leaf, or one layer of a stacked leaf: :func:`quantize` by default, the
    fused kernel ``repro.kernels.ops.dps_quantize_leaf`` in a TPU train
    step.

    Leaves are SERIALIZED with ``optimization_barrier``: each quantization
    event's temporaries (the u32 random-bits tensor + fp32 working copies,
    ~6× the leaf in bytes) are live one leaf at a time instead of
    concurrently.  The buffer-assignment dump of the 236B-MoE train step
    showed ~19 GiB of co-scheduled quantization temporaries without this;
    with the chain the peak is one leaf's worth.  (A reshape-into-chunks
    variant is NOT usable here: flattening a sharded leaf makes XLA gather
    the full logical tensor on every device.)
    """
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out, stats = [], QuantStats.zero()
    dep = jnp.zeros((), jnp.float32)
    for i, (path, leaf) in enumerate(leaves):
        if predicate is not None and not predicate(path, leaf):
            out.append(leaf)
            continue
        leaf_d, _ = jax.lax.optimization_barrier((leaf, dep))
        k = jax.random.fold_in(key, i) if key is not None else None
        q, s = _quantize_leaf(leaf_d, fmt, mode, k, quantize_fn)
        out.append(q)
        stats = stats.merge(s)
        dep = s.count
    return jax.tree_util.tree_unflatten(treedef, [v for v in out]), stats


def _quantize_leaf(leaf: jax.Array, fmt: FixedPointFormat, mode: str, key,
                   quantize_fn):
    """Quantize one tree leaf with bounded temporaries.

    Layer-stacked weights (ndim ≥ 3, leading dim = layers, never sharded)
    are processed per-layer under ``lax.map``: the u32 random-bits tensor
    and the fp32 working copies are then one layer-slice each instead of
    one full-stack each (~7× leaf bytes — the dominant train-step
    temporary at 100B+ scale).  ``lax.map`` over the UNSHARDED leading axis
    keeps every slice's sharding; flattening a sharded leaf instead would
    all-gather it (measured: 2.6 TB temp on the 236B MoE).
    """
    if leaf.ndim >= 3 and leaf.shape[0] > 4 and leaf.size > (1 << 22):
        keys = (jax.random.split(key, leaf.shape[0]) if key is not None
                else jnp.zeros((leaf.shape[0], 2), jnp.uint32))

        def body(xs):
            sl, k = xs
            return quantize_fn(sl, fmt, mode=mode,
                               key=k if key is not None else None)

        q, s = jax.lax.map(body, (leaf, keys))
        return q, QuantStats(
            count=jnp.sum(s.count), nonzero=jnp.sum(s.nonzero),
            overflow=jnp.sum(s.overflow), abs_err_sum=jnp.sum(s.abs_err_sum),
            rel_err_sum=jnp.sum(s.rel_err_sum), abs_sum=jnp.sum(s.abs_sum),
            max_abs=jnp.max(s.max_abs))
    return quantize_fn(leaf, fmt, mode=mode, key=key)
