"""Pallas kernel sweep: dps_quant vs the pure-jnp oracle (bit-exact)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fixed_point import FixedPointFormat
from repro.kernels import ops
from repro.kernels.dps_quant import dps_quant_pallas, dps_quant_wire_pallas
from repro.kernels.ref import (dps_quant_ref, dps_quant_wire_ref,
                               stats_from_vector)

SHAPES_2D = [(8, 128), (256, 1024), (300, 1100), (1, 7), (513, 129)]
FMTS = [(4, 2), (8, 8), (2, 14), (6, 10), (16, 9)]


def _bits(key, shape):
    return jax.random.bits(key, shape=shape, dtype=jnp.uint32)


@pytest.mark.parametrize("shape", SHAPES_2D)
@pytest.mark.parametrize("ilfl", [(4, 2), (6, 10)])
def test_kernel_matches_ref_stochastic(shape, ilfl):
    il, fl = ilfl
    key = jax.random.key(hash(shape) % 1000)
    x = jax.random.normal(key, shape) * (2.0 ** (il - 2))
    bits = _bits(jax.random.fold_in(key, 1), shape)
    fmt3 = jnp.array([il, fl, 0], jnp.int32)

    q_k, vec_k = dps_quant_pallas(x, fmt3, bits, interpret=True)
    q_r, vec_r = dps_quant_ref(x, il, fl, bits)

    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    np.testing.assert_allclose(np.asarray(vec_k), np.asarray(vec_r),
                               rtol=1e-6, atol=1e-4)


@pytest.mark.parametrize("ilfl", FMTS)
def test_kernel_matches_ref_nearest(ilfl):
    il, fl = ilfl
    key = jax.random.key(il * 31 + fl)
    x = jax.random.normal(key, (256, 1024)) * (2.0 ** (il - 2))
    bits = jnp.zeros((256, 1024), jnp.uint32)
    fmt3 = jnp.array([il, fl, 0], jnp.int32)
    q_k, vec_k = dps_quant_pallas(x, fmt3, bits, stochastic=False,
                                   interpret=True)
    q_r, vec_r = dps_quant_ref(x, il, fl, bits, mode="nearest")
    np.testing.assert_array_equal(np.asarray(q_k), np.asarray(q_r))
    np.testing.assert_allclose(np.asarray(vec_k), np.asarray(vec_r),
                               rtol=1e-6, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_kernel_dtypes(dtype):
    key = jax.random.key(3)
    x = (jax.random.normal(key, (64, 256)) * 4).astype(dtype)
    bits = _bits(jax.random.fold_in(key, 1), (64, 256))
    fmt3 = jnp.array([5, 6, 0], jnp.int32)
    q_k, vec_k = dps_quant_pallas(x, fmt3, bits, interpret=True)
    q_r, vec_r = dps_quant_ref(x, 5, 6, bits)
    assert q_k.dtype == dtype
    np.testing.assert_array_equal(np.asarray(q_k, np.float32),
                                  np.asarray(q_r, np.float32))
    np.testing.assert_allclose(np.asarray(vec_k), np.asarray(vec_r), rtol=1e-5)


@pytest.mark.parametrize("shape", [(17,), (3, 5, 7), (2, 3, 4, 5), (4096,),
                                   (1025, 3)])
def test_ops_arbitrary_rank_matches_core(shape):
    """ops.dps_quantize == core.quantize for any rank (same bits)."""
    from repro.core.fixed_point import quantize
    key = jax.random.key(11)
    x = jax.random.normal(key, shape) * 8
    n = x.size
    bits = jax.random.bits(jax.random.fold_in(key, 5), shape=(n,),
                           dtype=jnp.uint32)
    fmt = FixedPointFormat.create(5, 7)
    q_o, s_o = ops.dps_quantize(x, fmt, bits=bits)
    q_c, s_c = quantize(x, fmt, bits=bits.reshape(shape))
    np.testing.assert_array_equal(np.asarray(q_o), np.asarray(q_c))
    assert float(s_o.count) == n
    np.testing.assert_allclose(float(s_o.abs_err_sum), float(s_c.abs_err_sum),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(s_o.overflow), float(s_c.overflow))


def test_ops_padding_excluded_from_stats():
    """Padded tail lanes must not contaminate count/nonzero."""
    x = jnp.ones((1000,)) * 0.37          # minor dim pads 1000 -> 1024... n<1024 so minor=1000
    x = jnp.ones((1500,)) * 0.37          # forces pad with minor=1024
    fmt = FixedPointFormat.create(4, 2)
    q, s = ops.dps_quantize(x, fmt, stochastic=False)
    assert float(s.count) == 1500
    assert float(s.nonzero) == 1500


def test_kernel_dynamic_fmt_single_compile():
    """fmt3 is a runtime operand: two formats share one executable."""
    key = jax.random.key(4)
    x = jax.random.normal(key, (256, 1024))
    bits = _bits(key, (256, 1024))
    f = jax.jit(lambda x, fmt3, bits: dps_quant_pallas(x, fmt3, bits,
                                                        interpret=True))
    q1, _ = f(x, jnp.array([4, 2, 0], jnp.int32), bits)
    q2, _ = f(x, jnp.array([8, 12, 0], jnp.int32), bits)
    # finer grid -> strictly smaller (or equal) error
    e1 = float(jnp.abs(q1 - x).sum())
    e2 = float(jnp.abs(q2 - x).sum())
    assert e2 < e1


# ---------------------------------------------------------------------------
# Fused wire variant (int8 grid-integer payload).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(256, 1024), (300, 1100), (17, 33)])
@pytest.mark.parametrize("ilfl", [(3, 5), (2, 6)])
def test_wire_kernel_matches_ref_stochastic(shape, ilfl):
    il, fl = ilfl
    key = jax.random.key(il * 131 + fl)
    x = jax.random.normal(key, shape) * (2.0 ** (il - 1))
    bits = _bits(jax.random.fold_in(key, 1), shape)
    fmt3 = jnp.array([il, fl, 0], jnp.int32)
    w_k, vec_k = dps_quant_wire_pallas(x, fmt3, bits, interpret=True)
    w_r, vec_r = dps_quant_wire_ref(x, il, fl, bits)
    assert w_k.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(w_k), np.asarray(w_r))
    np.testing.assert_allclose(np.asarray(vec_k), np.asarray(vec_r),
                               rtol=1e-6, atol=1e-4)


def test_wire_kernel_saturates_overwide_format_into_overflow():
    """IL + FL > 8: grid integers beyond ±127 saturate and count as
    overflow — bit-exact between kernel and reference."""
    key = jax.random.key(7)
    x = jax.random.normal(key, (256, 1024)) * 4.0   # y = x·2^8 well past 127
    bits = _bits(jax.random.fold_in(key, 1), (256, 1024))
    fmt3 = jnp.array([8, 8, 0], jnp.int32)
    w_k, vec_k = dps_quant_wire_pallas(x, fmt3, bits, interpret=True)
    w_r, vec_r = dps_quant_wire_ref(x, 8, 8, bits)
    np.testing.assert_array_equal(np.asarray(w_k), np.asarray(w_r))
    np.testing.assert_allclose(np.asarray(vec_k), np.asarray(vec_r),
                               rtol=1e-6, atol=1e-4)
    assert float(vec_k[2]) > 0.0                     # saturation counted
    w = np.asarray(w_k, np.int32)
    assert w.max() == 127 and w.min() == -128        # pinned at capacity


@pytest.mark.parametrize("shape", [(17,), (3, 5, 7), (1500,)])
def test_ops_wire_matches_ref_and_masks_padding(shape):
    key = jax.random.key(13)
    x = jax.random.normal(key, shape) * 2
    n = x.size
    bits = jax.random.bits(jax.random.fold_in(key, 5), shape=(n,),
                           dtype=jnp.uint32)
    fmt = FixedPointFormat.create(3, 5)
    w_o, s_o = ops.dps_quantize_wire(x, fmt, bits=bits)
    w_r, vec_r = dps_quant_wire_ref(x.reshape(-1), 3, 5, bits)
    assert w_o.shape == shape and w_o.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(w_o.reshape(-1)),
                                  np.asarray(w_r))
    assert float(s_o.count) == n                     # padding masked out
    np.testing.assert_allclose(float(s_o.abs_err_sum), float(vec_r[3]),
                               rtol=1e-5, atol=1e-5)


def test_wire_kernel_dynamic_fmt_single_compile():
    """⟨IL, FL⟩ rides the SMEM scalar prefetch: per-step format changes
    reuse the compiled wire kernel."""
    key = jax.random.key(4)
    x = jax.random.normal(key, (256, 1024))
    bits = _bits(key, (256, 1024))
    f = jax.jit(lambda x, fmt3, bits: dps_quant_wire_pallas(x, fmt3, bits,
                                                             interpret=True))
    w1, _ = f(x, jnp.array([3, 5, 0], jnp.int32), bits)
    w2, _ = f(x, jnp.array([2, 6, 0], jnp.int32), bits)
    assert f._cache_size() == 1          # one executable, two formats
    # and each wire matches its format's reference encode
    for w, (il, fl) in ((w1, (3, 5)), (w2, (2, 6))):
        w_r, _ = dps_quant_wire_ref(x, il, fl, bits)
        np.testing.assert_array_equal(np.asarray(w), np.asarray(w_r))


def test_onchip_prng_wire_variant_traces():
    """The TPU PRNG wire path must trace with int8 outputs (see
    test_onchip_prng_variant_traces for why eval_shape is the CPU-side
    bound)."""
    x = jax.ShapeDtypeStruct((256, 1024), jnp.float32)
    fmt3 = jax.ShapeDtypeStruct((3,), jnp.int32)
    bits = jax.ShapeDtypeStruct((256, 1024), jnp.uint32)
    w, stats = jax.eval_shape(
        lambda x, fmt3, bits: dps_quant_wire_pallas(
            x, fmt3, bits, use_onchip_prng=True, interpret=False),
        x, fmt3, bits)
    assert w.shape == (256, 1024) and w.dtype == jnp.int8
    assert stats.shape == (7,) and stats.dtype == jnp.float32


# ---------------------------------------------------------------------------
# Grouped wire kernel ([G, 2] SMEM format table) + fused decode-reduce.
# ---------------------------------------------------------------------------

from repro.kernels.dps_quant import (DEFAULT_GROUP_QUANTUM, MIN_GROUP_QUANTUM,
                                     dps_quant_group_wire_pallas, group_block,
                                     dps_wire_reduce_pallas)
from repro.kernels.ref import (dps_quant_group_wire_ref, dps_wire_reduce_ref,
                               stats_from_matrix)


def _grouped_operands(seed, tile_groups, quantum, holes=0):
    """(x, bits, mask) for a group-aligned buffer of len(tile_groups) tiles;
    ``holes`` masks that many trailing elements of each group's last tile
    (the alignment-padding pattern)."""
    tg = np.asarray(tile_groups, np.int32)
    L = tg.size * quantum
    key = jax.random.key(seed)
    x = jax.random.normal(key, (L,)) * 2.0
    bits = jax.random.bits(jax.random.fold_in(key, 1), shape=(L,),
                           dtype=jnp.uint32)
    mask = np.ones((L,), np.float32)
    if holes:
        for g in np.unique(tg):
            last = np.nonzero(tg == g)[0].max()
            mask[(last + 1) * quantum - holes:(last + 1) * quantum] = 0.0
    return x, bits, jnp.asarray(mask), jnp.asarray(tg)


@pytest.mark.parametrize("tiles_spec, ilfl", [
    ([0, 0, 1, 2, 2], ([3, 2, 4], [5, 6, 4])),
    ([0], ([2], [6])),
    ([1, 0, 1, 0], ([4, 3], [4, 5])),     # interleaved tile->group map
])
def test_grouped_wire_kernel_matches_ref(tiles_spec, ilfl):
    il, fl = ilfl
    Q = DEFAULT_GROUP_QUANTUM
    x, bits, mask, tg = _grouped_operands(7, tiles_spec, Q, holes=13)
    fmt_tab = jnp.stack([jnp.array(il, jnp.int32),
                         jnp.array(fl, jnp.int32)], axis=1)
    for stochastic in (True, False):
        w_k, mat_k = dps_quant_group_wire_pallas(
            x, fmt_tab, tg, jnp.zeros((1,), jnp.int32), bits, mask,
            stochastic=stochastic, quantum=Q, interpret=True)
        w_r, mat_r = dps_quant_group_wire_ref(
            x, jnp.array(il), jnp.array(fl), tg, bits, mask, Q,
            mode="stochastic" if stochastic else "nearest")
        assert w_k.dtype == jnp.int8
        np.testing.assert_array_equal(np.asarray(w_k), np.asarray(w_r))
        np.testing.assert_allclose(np.asarray(mat_k), np.asarray(mat_r),
                                   rtol=1e-6, atol=1e-4)


def test_grouped_wire_kernel_matches_global_kernel_per_group():
    """A [G] table must reproduce G independent global-format wire-kernel
    calls on the per-group slices (same elements, same bits)."""
    Q = DEFAULT_GROUP_QUANTUM
    tiles = [0, 0, 1, 2]
    il, fl = [3, 2, 4], [5, 6, 4]
    x, bits, mask, tg = _grouped_operands(3, tiles, Q)
    fmt_tab = jnp.stack([jnp.array(il, jnp.int32),
                         jnp.array(fl, jnp.int32)], axis=1)
    w_g, mat_g = dps_quant_group_wire_pallas(
        x, fmt_tab, tg, jnp.zeros((1,), jnp.int32), bits, mask, quantum=Q,
        interpret=True)
    bounds = [(0, 2 * Q), (2 * Q, 3 * Q), (3 * Q, 4 * Q)]
    for g, (lo, hi) in enumerate(bounds):
        fmt3 = jnp.array([il[g], fl[g], 0], jnp.int32)
        w_i, vec_i = dps_quant_wire_pallas(
            np.asarray(x[lo:hi]).reshape(-1, 128), fmt3,
            np.asarray(bits[lo:hi]).reshape(-1, 128), interpret=True)
        np.testing.assert_array_equal(np.asarray(w_g[lo:hi]),
                                      np.asarray(w_i).reshape(-1))
        np.testing.assert_allclose(np.asarray(mat_g[g]), np.asarray(vec_i),
                                   rtol=1e-5, atol=1e-4)


def test_group_block_quantum_validation():
    assert group_block(4096) == (32, 128)
    assert group_block(32768) == (32, 1024)
    assert group_block(262144) == (256, 1024)
    with pytest.raises(ValueError, match="multiple"):
        group_block(1024)
    assert MIN_GROUP_QUANTUM == 4096


def test_wire_reduce_kernel_matches_ref_and_jnp_mean():
    """The fused decode-reduce == per-element decode + mean, bit-exactly
    (every decoded value is an exact fp32 multiple of its group's 2^-FL)."""
    Q = DEFAULT_GROUP_QUANTUM
    n, tiles = 8, 3
    key = jax.random.key(5)
    wire = jax.random.randint(key, (n, tiles * Q), -128, 128, jnp.int8)
    fl = jnp.array([5, 2, 7], jnp.int32)
    tg = jnp.array([0, 2, 1], jnp.int32)
    fmt_tab = jnp.stack([jnp.array([3, 6, 1], jnp.int32), fl], axis=1)
    out = dps_wire_reduce_pallas(wire, fmt_tab, tg, quantum=Q, interpret=True)
    ref = dps_wire_reduce_ref(wire, fl, tg, Q)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))
    # against the naive jnp decode-then-mean
    inv = np.asarray([2.0 ** -5, 2.0 ** -7, 2.0 ** -2], np.float32)
    dec = np.asarray(wire, np.float32).reshape(n, tiles, Q) * inv[None, :,
                                                                  None]
    np.testing.assert_array_equal(np.asarray(out),
                                  (dec.sum(0) / n).reshape(-1))


def test_grouped_kernel_onchip_prng_traces():
    """The TPU PRNG grouped variant must trace with int8 wire + [G, 7]
    stats (execution needs real TPU; see test_onchip_prng_variant_traces)."""
    Q = DEFAULT_GROUP_QUANTUM
    x = jax.ShapeDtypeStruct((4 * Q,), jnp.float32)
    tab = jax.ShapeDtypeStruct((3, 2), jnp.int32)
    tg = jax.ShapeDtypeStruct((4,), jnp.int32)
    seed = jax.ShapeDtypeStruct((1,), jnp.int32)
    bits = jax.ShapeDtypeStruct((4 * Q,), jnp.uint32)
    mask = jax.ShapeDtypeStruct((4 * Q,), jnp.float32)
    w, stats = jax.eval_shape(
        lambda *a: dps_quant_group_wire_pallas(
            *a, use_onchip_prng=True, quantum=Q, interpret=False),
        x, tab, tg, seed, bits, mask)
    assert w.shape == (4 * Q,) and w.dtype == jnp.int8
    assert stats.shape == (3, 7) and stats.dtype == jnp.float32


def test_pallas_quant_skips_noop_pads():
    """Tile-aligned shapes must not pay the three pad copies (satellite:
    _pallas_quant padded x/bits/mask even when already aligned)."""
    x = jax.ShapeDtypeStruct((256, 1024), jnp.float32)
    fmt3 = jax.ShapeDtypeStruct((3,), jnp.int32)
    bits = jax.ShapeDtypeStruct((256, 1024), jnp.uint32)
    jaxpr = jax.make_jaxpr(
        lambda x, fmt3, bits: dps_quant_pallas(x, fmt3, bits))(x, fmt3, bits)
    assert "pad[" not in str(jaxpr)
    # and a genuinely ragged shape still pads (the mask keeps stats clean)
    xr = jax.ShapeDtypeStruct((300, 1100), jnp.float32)
    br = jax.ShapeDtypeStruct((300, 1100), jnp.uint32)
    jaxpr_r = jax.make_jaxpr(
        lambda x, fmt3, bits: dps_quant_pallas(x, fmt3, bits))(xr, fmt3, br)
    assert "pad[" in str(jaxpr_r)


def test_onchip_prng_variant_traces():
    """The TPU PRNG path must trace (kernel jaxpr builds; execution needs TPU).

    JAX 0.8 refuses to *lower* non-interpret Pallas on the CPU backend, so
    abstract evaluation is the strongest CPU-side check: it proves the kernel
    body (incl. ``pltpu.prng_seed``/``prng_random_bits``) is trace-valid and
    output shapes/dtypes are right.  Full lowering is exercised on real TPU.
    """
    x = jax.ShapeDtypeStruct((256, 1024), jnp.float32)
    fmt3 = jax.ShapeDtypeStruct((3,), jnp.int32)
    bits = jax.ShapeDtypeStruct((256, 1024), jnp.uint32)
    q, stats = jax.eval_shape(
        lambda x, fmt3, bits: dps_quant_pallas(
            x, fmt3, bits, use_onchip_prng=True, interpret=False),
        x, fmt3, bits)
    assert q.shape == (256, 1024) and q.dtype == jnp.float32
    assert stats.shape == (7,) and stats.dtype == jnp.float32
    # and the documented CPU limitation holds (so nobody silently "runs" it):
    f = jax.jit(lambda x, fmt3, bits: dps_quant_pallas(
        x, fmt3, bits, use_onchip_prng=True, interpret=False))
    with pytest.raises(Exception, match="[Ii]nterpret"):
        f.lower(x, fmt3, bits)
