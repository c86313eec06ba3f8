"""Distribution tests that need >1 device: run in a subprocess with
``xla_force_host_platform_device_count`` (the main pytest process must keep
seeing 1 device so smoke tests reflect the container).

Covers: compressed DPS all-reduce (wire format + numerics + stats), stat
psum, MoE all-to-all path vs the einsum oracle, sharded train-step
equivalence vs single-device, elastic checkpoint restore across meshes.
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_dps_allreduce_mean_matches_exact():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core.fixed_point import FixedPointFormat
        from repro.dist.collectives import dps_allreduce_mean, psum_stats
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        fmt = FixedPointFormat.create(3, 5)   # IL+FL=8 -> int8 payload
        key = jax.random.key(0)
        x = jax.random.normal(key, (8, 1000)) * 0.5

        def body(xs, key):
            m, stats = dps_allreduce_mean(xs[0], fmt, "data", key)
            return m, psum_stats(stats, "data").count

        f = jax.jit(jax.shard_map(body, mesh=mesh,
                    in_specs=(P("data", None), P()),
                    out_specs=(P(), P()), check_vma=False))
        mean, count = f(x, key)
        exact = np.asarray(x, np.float64).mean(0)
        # wire quantization error bounded by ~2 grid steps (two rounds)
        err = np.abs(np.asarray(mean) - exact).max()
        assert err < 2 * 2.0**-5 + 1e-6, err
        assert float(count) == 8000.0
        print("OK")
    """)


def test_dps_allreduce_bytes_are_int8():
    """The wire payload must actually be int8 in the compiled HLO."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, re
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.core.fixed_point import FixedPointFormat
        from repro.dist.collectives import dps_allreduce_mean
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        fmt = FixedPointFormat.create(3, 5)

        def body(xs, key):
            m, _ = dps_allreduce_mean(xs[0], fmt, "data", key)
            return m

        f = jax.jit(jax.shard_map(body, mesh=mesh,
                    in_specs=(P("data", None), P()),
                    out_specs=P(), check_vma=False))
        txt = f.lower(jax.ShapeDtypeStruct((8, 4096), jnp.float32),
                      jax.ShapeDtypeStruct((), jax.random.key(0).dtype)
                      ).compile().as_text()
        a2a = [l for l in txt.splitlines() if "all-to-all" in l and "s8[" in l]
        ag = [l for l in txt.splitlines() if "all-gather" in l and "s8[" in l]
        print("A2A_INT8", len(a2a) > 0, "AG_INT8", len(ag) > 0)
    """)
    assert "A2A_INT8 True" in out and "AG_INT8 True" in out


def test_wire_codec_roundtrip_int8_cpu():
    """Direct unit test of the int8 wire format (single process, no mesh) —
    complements the HLO-text inspection in test_dps_allreduce_bytes_are_int8:
    the payload dtype, the per-element error bound, and grid idempotence."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.fixed_point import FixedPointFormat
    from repro.dist.collectives import wire_decode, wire_encode

    fmt = FixedPointFormat.create(3, 5)        # IL+FL=8 -> int8 wire
    key = jax.random.key(0)
    x = jax.random.normal(key, (513,)) * 0.5

    wire, stats = wire_encode(x, fmt, key=jax.random.fold_in(key, 1))
    assert wire.dtype == jnp.int8
    assert float(stats.count) == x.size
    dec = wire_decode(wire, fmt)
    # stochastic rounding: strictly less than one grid step from the
    # range-clipped value, element-wise
    clipped = jnp.clip(x, -4.0, 4.0 - 2.0 ** -5)
    assert float(jnp.abs(dec - clipped).max()) < 2.0 ** -5 + 1e-7

    # every representable grid integer survives encode(decode(w)) bit-exactly
    grid = jnp.arange(-128, 128, dtype=jnp.int8)
    w2, _ = wire_encode(wire_decode(grid, fmt), fmt,
                        key=jax.random.fold_in(key, 2))
    np.testing.assert_array_equal(np.asarray(w2), np.asarray(grid))


def test_wire_encode_rejects_overwide_static_format():
    """IL + FL > 8 with concrete widths must fail eagerly, not saturate."""
    import jax
    import pytest
    from repro.core.fixed_point import FixedPointFormat
    from repro.dist.collectives import wire_encode

    x = jax.numpy.ones((16,))
    with pytest.raises(ValueError, match="exceeds the int8 wire"):
        wire_encode(x, FixedPointFormat.create(4, 8), key=jax.random.key(0))


def test_wire_encode_traced_overwide_counts_saturation_as_overflow():
    """Traced formats can't be rejected statically: saturated elements must
    surface in QuantStats.overflow so the controller sees wire clipping."""
    import jax
    import jax.numpy as jnp
    from repro.core.fixed_point import FixedPointFormat
    from repro.dist.collectives import wire_encode

    def enc(x, il, fl):
        wire, s = wire_encode(x, FixedPointFormat(il, fl), mode="nearest")
        return wire, s.overflow

    # <4,8>: x=0.9 -> grid integer 230 > 127 -> saturates, every element
    wire, over = jax.jit(enc)(jnp.full((64,), 0.9), jnp.int32(4), jnp.int32(8))
    assert float(over) == 64.0
    assert int(jnp.abs(wire.astype(jnp.int32)).max()) == 127
    # same format, in-range x: no saturation, no overflow
    _, over2 = jax.jit(enc)(jnp.full((64,), 0.25), jnp.int32(4), jnp.int32(8))
    assert float(over2) == 0.0


def test_wire_encode_per_group_matches_independent_calls():
    """[G]-shaped ⟨IL, FL⟩ == G independent global-format calls on the
    contiguous chunks, element- and stat-exact — including the
    non-divisible last-group boundary (1000 = 2·334 + 332)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.fixed_point import FixedPointFormat
    from repro.dist.collectives import wire_decode, wire_encode

    n, il, fl = 1000, [3, 2, 4], [5, 6, 4]
    key = jax.random.key(0)
    x = jax.random.normal(key, (n,)) * 0.7
    bits = jax.random.bits(jax.random.fold_in(key, 1), shape=(n,),
                           dtype=jnp.uint32)
    fmt_g = FixedPointFormat(jnp.array(il, jnp.int32),
                             jnp.array(fl, jnp.int32))

    for mode, b in (("stochastic", bits), ("nearest", None)):
        wg, sg = wire_encode(x, fmt_g, bits=b, mode=mode)
        assert wg.shape == x.shape and wg.dtype == jnp.int8
        dec_g = wire_decode(wg, fmt_g)
        chunk = -(-n // 3)
        for g in range(3):
            lo, hi = g * chunk, min((g + 1) * chunk, n)
            f = FixedPointFormat.create(il[g], fl[g])
            wi, si = wire_encode(x[lo:hi], f,
                                 bits=b[lo:hi] if b is not None else None,
                                 mode=mode)
            np.testing.assert_array_equal(np.asarray(wg[lo:hi]),
                                          np.asarray(wi))
            for field in ("count", "nonzero", "overflow", "abs_err_sum",
                          "rel_err_sum", "abs_sum", "max_abs"):
                np.testing.assert_allclose(
                    float(getattr(sg, field)[g]), float(getattr(si, field)),
                    rtol=1e-6, atol=1e-5)
            np.testing.assert_array_equal(np.asarray(dec_g[lo:hi]),
                                          np.asarray(wire_decode(wi, f)))


def test_wire_encode_rejects_unknown_mode_on_both_backends():
    """A typo'd rounding mode must raise identically on the jnp and the
    kernel backend (the kernel folds mode into a boolean internally and
    would otherwise silently round to nearest)."""
    import jax
    import pytest
    from repro.core.fixed_point import FixedPointFormat
    from repro.dist.collectives import wire_encode

    x = jax.numpy.ones((16,))
    fmt = FixedPointFormat.create(3, 5)
    for backend in ("jnp", "kernel"):
        with pytest.raises(ValueError, match="rounding mode"):
            wire_encode(x, fmt, key=jax.random.key(0), mode="stochastc",
                        backend=backend)


def test_wire_codec_backends_bitexact():
    """The fused-kernel codec (interpret mode here) and the jnp codec draw
    the same rounding bits from the same key, so wire and stats agree."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.fixed_point import FixedPointFormat
    from repro.dist.collectives import wire_encode

    fmt = FixedPointFormat.create(3, 5)
    key = jax.random.key(9)
    x = jax.random.normal(key, (2000,)) * 0.5
    w_j, s_j = wire_encode(x, fmt, key=jax.random.fold_in(key, 1),
                           backend="jnp")
    w_k, s_k = wire_encode(x, fmt, key=jax.random.fold_in(key, 1),
                           backend="kernel")
    np.testing.assert_array_equal(np.asarray(w_j), np.asarray(w_k))
    for field in ("count", "overflow", "abs_err_sum", "max_abs"):
        np.testing.assert_allclose(float(getattr(s_j, field)),
                                   float(getattr(s_k, field)), rtol=1e-6)


def test_dps_allreduce_mean_single_device_inprocess():
    """dps_allreduce_mean end-to-end on this process's 1-device mesh: the
    degenerate collectives still run and the result lands on the wire grid."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.fixed_point import FixedPointFormat
    from repro.dist.collectives import dps_allreduce_mean, psum_stats
    from repro.dist.sharding import make_mesh

    mesh = make_mesh((1,), ("data",))
    fmt = FixedPointFormat.create(3, 5)
    x = jax.random.normal(jax.random.key(3), (1, 257)) * 0.5

    def body(xs, key):
        m, stats = dps_allreduce_mean(xs[0], fmt, "data", key)
        return m, psum_stats(stats, "data").count

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=(P("data", None), P()),
                              out_specs=(P(), P()), check_vma=False))
    mean, count = f(x, jax.random.key(4))
    assert float(count) == 257.0
    # n=1: the "mean" is x quantized twice; both quantizations land on the
    # same ⟨3,5⟩ grid so the result is within one step of x and grid-exact
    assert float(jnp.abs(mean - x[0]).max()) < 2.0 ** -5 + 1e-7
    scaled = jnp.asarray(mean, jnp.float32) * 32.0
    assert float(jnp.abs(scaled - jnp.round(scaled)).max()) == 0.0


def test_wire_codec_roundtrip_property():
    """Property-style sweep of the wire codec: random ⟨IL, FL⟩ formats
    (IL + FL ≤ 8), group counts and shapes — including non-divisible
    per-group remainders — must round-trip with error ≤ 2^-FL against the
    range-clipped input, for both rounding modes; scalar formats must be
    bit-identical across the jnp and kernel backends."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.fixed_point import FixedPointFormat
    from repro.dist.collectives import wire_decode, wire_encode

    rng = np.random.RandomState(0)
    for trial in range(20):
        groups = int(rng.choice([0, 0, 0, 1, 2, 3, 5]))  # 0 = scalar format
        il = rng.randint(1, 8, size=max(groups, 1))
        fl = np.array([rng.randint(1, 9 - i) for i in il])
        n = int(rng.choice([1, 7, 64, 333, 1000, 4097]))
        if groups:
            fmt = FixedPointFormat(jnp.asarray(il, jnp.int32),
                                   jnp.asarray(fl, jnp.int32))
        else:
            fmt = FixedPointFormat.create(int(il[0]), int(fl[0]))
        key = jax.random.key(trial)
        span = 2.0 ** (il.max() - 1)
        x = (jax.random.normal(key, (n,)) * span * 0.75).astype(jnp.float32)

        for mode in ("stochastic", "nearest"):
            wire, stats = wire_encode(x, fmt, key=jax.random.fold_in(key, 1),
                                      mode=mode)
            assert wire.dtype == jnp.int8 and wire.shape == x.shape
            dec = np.asarray(wire_decode(wire, fmt), np.float64)
            # per-element reference: clip to each group's representable
            # range, then the rounding error is < one grid step 2^-FL
            # (≤ half a step for nearest)
            xn = np.asarray(x, np.float64)
            chunk = -(-n // max(groups, 1))
            err_ok = True
            for g in range(max(groups, 1)):
                lo, hi = g * chunk, min((g + 1) * chunk, n)
                if lo >= n:
                    continue
                step = 2.0 ** -float(fl[g])
                top = 2.0 ** (float(il[g]) - 1)
                ref = np.clip(xn[lo:hi], -top, top - step)
                bound = step * (0.5 if mode == "nearest" else 1.0) + 1e-9
                err_ok &= bool(np.abs(dec[lo:hi] - ref).max() <= bound)
            assert err_ok, (trial, mode, il, fl, n)
            assert float(stats.count.sum()) == n

        if not groups:
            # backends draw the same rounding bits from the same key
            w_j, s_j = wire_encode(x, fmt, key=jax.random.fold_in(key, 1),
                                   backend="jnp")
            w_k, s_k = wire_encode(x, fmt, key=jax.random.fold_in(key, 1),
                                   backend="kernel")
            np.testing.assert_array_equal(np.asarray(w_j), np.asarray(w_k))
            np.testing.assert_allclose(float(s_j.abs_err_sum),
                                       float(s_k.abs_err_sum), rtol=1e-6)


def test_wire_codec_grouped_property_sweep():
    """Satellite property sweep: for random ⟨IL, FL⟩ tables, group counts
    and shapes (equal-chunk and explicit non-divisible group_sizes), the
    grouped KERNEL codec ≡ the grouped jnp codec ≡ G independent
    global-format calls on the per-group slices — wire bytes bit-exact,
    stats allclose, decode round-trips through both backends."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.fixed_point import FixedPointFormat
    from repro.dist.collectives import wire_decode, wire_encode

    rng = np.random.RandomState(7)
    for trial in range(12):
        groups = int(rng.randint(1, 6))
        il = rng.randint(1, 8, size=groups)
        fl = np.array([rng.randint(1, 9 - i) for i in il])
        fmt = FixedPointFormat(jnp.asarray(il, jnp.int32),
                               jnp.asarray(fl, jnp.int32))
        if rng.rand() < 0.5:
            # explicit per-layer group sizes (non-divisible on purpose)
            sizes = tuple(int(s) for s in rng.randint(1, 5000, size=groups))
            n = sum(sizes)
        else:
            # the equal-chunk default split
            sizes = None
            n = int(rng.choice([7, 333, 1000, 4097]))
        key = jax.random.key(100 + trial)
        x = (jax.random.normal(key, (n,))
             * (2.0 ** (il.max() - 1)) * 0.75).astype(jnp.float32)
        bits = jax.random.bits(jax.random.fold_in(key, 1), shape=(n,),
                               dtype=jnp.uint32)

        for mode in ("stochastic", "nearest"):
            b = bits if mode == "stochastic" else None
            w_j, s_j = wire_encode(x, fmt, bits=b, mode=mode, backend="jnp",
                                   group_sizes=sizes)
            w_k, s_k = wire_encode(x, fmt, bits=b, mode=mode,
                                   backend="kernel", group_sizes=sizes)
            np.testing.assert_array_equal(np.asarray(w_j), np.asarray(w_k))
            # independent per-group calls on the slices
            eff = sizes
            if eff is None:
                chunk = -(-n // groups)
                eff = tuple(max(0, min(chunk, n - g * chunk))
                            for g in range(groups))
            off = 0
            for g, sz in enumerate(eff):
                if not sz:
                    continue
                f_g = FixedPointFormat.create(int(il[g]), int(fl[g]))
                w_i, s_i = wire_encode(
                    x[off:off + sz], f_g,
                    bits=b[off:off + sz] if b is not None else None,
                    mode=mode)
                np.testing.assert_array_equal(np.asarray(w_j[off:off + sz]),
                                              np.asarray(w_i))
                for stats in (s_j, s_k):
                    for field in ("count", "nonzero", "overflow",
                                  "abs_err_sum", "rel_err_sum", "abs_sum",
                                  "max_abs"):
                        np.testing.assert_allclose(
                            float(getattr(stats, field)[g]),
                            float(getattr(s_i, field)),
                            rtol=1e-5, atol=1e-4,
                            err_msg=f"trial {trial} {mode} group {g} {field}")
                off += sz
            # grouped decode matches per-group decode
            dec = np.asarray(wire_decode(w_j, fmt, group_sizes=sizes))
            off = 0
            for g, sz in enumerate(eff):
                ref = np.asarray(w_j[off:off + sz], np.float32
                                 ) * 2.0 ** -float(fl[g])
                np.testing.assert_array_equal(dec[off:off + sz], ref)
                off += sz


def test_wire_encode_group_sizes_validation():
    import jax
    import jax.numpy as jnp
    import pytest
    from repro.core.fixed_point import FixedPointFormat
    from repro.dist.collectives import wire_encode

    fmt_g = FixedPointFormat(jnp.array([3, 3], jnp.int32),
                             jnp.array([5, 5], jnp.int32))
    x = jax.numpy.ones((10,))
    with pytest.raises(ValueError, match="group_sizes"):
        wire_encode(x, fmt_g, key=jax.random.key(0), group_sizes=(3, 3))
    with pytest.raises(ValueError, match="group_sizes"):
        wire_encode(x, FixedPointFormat.create(3, 5),
                    key=jax.random.key(0), group_sizes=(5, 5))


def test_grouped_allreduce_unequal_groups_matches_oracle_both_backends():
    """[G] formats with per-layer group_sizes through BOTH collective legs
    on 8 ranks: per-group error bounds against the numpy mean, [G] stats
    counting each global element once, and jnp/kernel backends
    bit-identical (the acceptance-criteria pin)."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.fixed_point import FixedPointFormat
        from repro.dist.collectives import dps_allreduce_mean, psum_stats
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        sizes = (5000, 37, 9000, 1)
        n = sum(sizes)
        il = [3, 2, 4, 3]; fl = [5, 6, 4, 5]
        fmt = FixedPointFormat(jnp.array(il, jnp.int32),
                               jnp.array(fl, jnp.int32))
        x = jax.random.normal(jax.random.key(0), (8, n)) * 0.5

        def make(backend):
            def body(xs, k):
                m, s = dps_allreduce_mean(xs[0], fmt, "data", k,
                                          backend=backend,
                                          group_sizes=sizes)
                st = psum_stats(s, "data")
                return m, st.count
            return jax.jit(jax.shard_map(body, mesh=mesh,
                           in_specs=(P("data", None), P()),
                           out_specs=(P(), P()), check_vma=False))

        key = jax.random.key(1)
        m_j, c_j = make("jnp")(x, key)
        m_k, c_k = make("kernel")(x, key)
        assert jnp.array_equal(m_j, m_k), "backends must be bit-identical"
        np.testing.assert_array_equal(np.asarray(c_j), np.asarray(c_k))
        np.testing.assert_allclose(np.asarray(c_j),
                                   np.array(sizes, np.float32) * 8)
        exact = np.asarray(x, np.float64).mean(0)
        offs = np.cumsum([0] + list(sizes))
        for g in range(4):
            lo, hi = offs[g], offs[g + 1]
            err = np.abs(np.asarray(m_j)[lo:hi] - exact[lo:hi]).max()
            assert err < 2 * 2.0 ** -float(fl[g]) + 1e-6, (g, err)
        print("OK")
    """)


def test_grouped_tree_allreduce_per_leaf_formats():
    """dps_allreduce_mean_tree with a [G] table = one ⟨IL, FL⟩ per leaf:
    per-leaf error bounds at that leaf's FL, [G] stats in leaf order, and
    a leaf-count mismatch raises."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.fixed_point import FixedPointFormat
        from repro.dist.collectives import dps_allreduce_mean_tree, psum_stats
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        # inputs inside the narrowest leaf range (b: <2, 6> holds |x| < 2):
        # the bound is on rounding error, clipping is overflow
        tree = {k: jnp.clip(jax.random.normal(jax.random.key(i), (8, n))
                            * 0.5, -1.9, 1.9)
                for i, (k, n) in enumerate((("a", 700), ("b", 3000),
                                            ("c", 5)))}
        fmt = FixedPointFormat(jnp.array([3, 2, 4], jnp.int32),
                               jnp.array([5, 6, 4], jnp.int32))
        specs = {k: P("data") for k in tree}

        def body(tr, k):
            m, s = dps_allreduce_mean_tree(tr, fmt, "data", k)
            return m, psum_stats(s, "data").count
        f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(specs, P()),
                                  out_specs=(P(), P()), check_vma=False))
        mean, count = f(tree, jax.random.key(3))
        np.testing.assert_allclose(np.asarray(count),
                                   np.array([700, 3000, 5]) * 8.0)
        for leaf, fl in (("a", 5), ("b", 6), ("c", 4)):
            exact = np.asarray(tree[leaf], np.float64).mean(0)
            err = np.abs(np.asarray(mean[leaf]) - exact).max()
            assert err < 2 * 2.0 ** -fl + 1e-6, (leaf, err)

        # wrong table height: informative error, not silent misuse
        bad = FixedPointFormat(jnp.array([3, 3], jnp.int32),
                               jnp.array([5, 5], jnp.int32))
        try:
            jax.jit(jax.shard_map(
                lambda tr, k: dps_allreduce_mean_tree(tr, bad, "data", k)[0],
                mesh=mesh, in_specs=(specs, P()), out_specs=P(),
                check_vma=False))(tree, jax.random.key(4))
            raise AssertionError("leaf-count mismatch must raise")
        except ValueError as e:
            assert "per leaf" in str(e), e
        print("OK")
    """)


def test_grouped_zero_half_collectives_match_oracle():
    """The ZeRO halves accept [G] formats now (the scalar-only ValueErrors
    are gone): reduce-scatter mean and params all-gather against numpy
    oracles with per-element group formats."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.fixed_point import FixedPointFormat
        from repro.dist.collectives import (dps_allgather_params,
                                            dps_reduce_scatter_mean,
                                            psum_stats)
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        n, per = 8, 1001
        sizes = (700, 301)
        fmt = FixedPointFormat(jnp.array([3, 2], jnp.int32),
                               jnp.array([5, 6], jnp.int32))
        x = jax.random.normal(jax.random.key(0), (n, per)) * 0.4

        def body(xs, key):
            shard, s1 = dps_reduce_scatter_mean(xs[0], fmt, "data", key,
                                                group_sizes=sizes)
            shards = jax.lax.all_gather(shard, "data", axis=0, tiled=True)
            full, s2 = dps_allgather_params(shard, fmt, "data",
                                            jax.random.fold_in(key, 1),
                                            group_sizes=None)
            return (shards, full, psum_stats(s1, "data").count,
                    psum_stats(s2, "data").count)

        f = jax.jit(jax.shard_map(body, mesh=mesh,
                    in_specs=(P("data", None), P()),
                    out_specs=(P(), P(), P(), P()), check_vma=False))
        shards, full, c1, c2 = f(x, jax.random.key(42))
        np.testing.assert_allclose(np.asarray(c1),
                                   np.array(sizes, np.float32) * 8)
        chunk = -(-per // n)
        exact = np.zeros((n * chunk,))
        exact[:per] = np.asarray(x, np.float64).mean(0)
        # per-position bound: the format of each element's group
        offs = np.cumsum([0] + list(sizes))
        step = np.full((n * chunk,), 2.0 ** -5)
        step[offs[1]:offs[2]] = 2.0 ** -6
        err = np.abs(np.asarray(shards) - exact)
        assert (err < step + 1e-6).all(), err.max()
        # the gather leg re-quantizes the shard once more (equal-chunk
        # default groups over the gathered vector)
        err2 = np.abs(np.asarray(full) - np.asarray(shards))
        assert (err2 < 2.0 ** -5 + 1e-6).all(), err2.max()
        print("OK")
    """)


def test_zero_halves_reject_explicit_kernel_backend_for_groups():
    """An explicit backend='kernel' with a [G] format must raise in the
    ZeRO halves (their chunk layout can't be tile-aligned), not silently
    degrade to the jnp codec — the satellite no-silent-degrade rule."""
    import jax
    import jax.numpy as jnp
    import pytest
    from jax.sharding import PartitionSpec as P
    from repro.core.fixed_point import FixedPointFormat
    from repro.dist.collectives import (dps_allgather_params,
                                        dps_reduce_scatter_mean)
    from repro.dist.sharding import make_mesh

    mesh = make_mesh((1,), ("data",))
    fmt = FixedPointFormat(jnp.array([3, 3], jnp.int32),
                           jnp.array([5, 5], jnp.int32))
    x = jnp.ones((64,))
    for coll in (dps_reduce_scatter_mean, dps_allgather_params):
        f = jax.shard_map(
            lambda xs, k: coll(xs, fmt, "data", k, backend="kernel")[0],
            mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False)
        with pytest.raises(ValueError, match="cannot be honored"):
            jax.jit(f)(x, jax.random.key(0))


def test_reduce_scatter_rejects_overwide_static_format():
    """IL + FL > 8 with concrete widths must fail eagerly through BOTH ZeRO
    half-collectives, exactly like the all-reduce path."""
    import jax
    import jax.numpy as jnp
    import pytest
    from jax.sharding import PartitionSpec as P
    from repro.core.fixed_point import FixedPointFormat
    from repro.dist.collectives import (dps_allgather_params,
                                        dps_reduce_scatter_mean)
    from repro.dist.sharding import make_mesh

    mesh = make_mesh((1,), ("data",))
    fmt = FixedPointFormat.create(4, 8)              # 12 bits > int8 wire
    x = jnp.ones((64,))
    for coll in (dps_reduce_scatter_mean, dps_allgather_params):
        f = jax.shard_map(lambda xs, k: coll(xs, fmt, "data", k)[0],
                          mesh=mesh, in_specs=(P(), P()), out_specs=P(),
                          check_vma=False)
        with pytest.raises(ValueError, match="exceeds the int8 wire"):
            jax.jit(f)(x, jax.random.key(0))


def test_reduce_scatter_traced_overwide_counts_overflow():
    """Traced over-wide formats can't be rejected statically: the saturated
    elements must surface in QuantStats.overflow through the reduce-scatter
    path so the controller sees the wire clipping (previously only the
    all-reduce path was covered)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.fixed_point import FixedPointFormat
    from repro.dist.collectives import (dps_allgather_params,
                                        dps_reduce_scatter_mean, psum_stats)
    from repro.dist.sharding import make_mesh

    mesh = make_mesh((1,), ("data",))

    def body(xs, il, fl, key):
        fmt = FixedPointFormat(il, fl)
        _, s1 = dps_reduce_scatter_mean(xs, fmt, "data", key,
                                        mode="nearest")
        _, s2 = dps_allgather_params(xs, fmt, "data", key, mode="nearest")
        return (psum_stats(s1, "data").overflow,
                psum_stats(s2, "data").overflow)

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=(P(), P(), P(), P()),
                              out_specs=(P(), P()), check_vma=False))
    # <4,8>: x=0.9 -> grid integer 230 > 127 -> saturates, every element
    o1, o2 = f(jnp.full((64,), 0.9), jnp.int32(4), jnp.int32(8),
               jax.random.key(0))
    assert float(o1) == 64.0 and float(o2) == 64.0
    # in-range values: no overflow
    o1, o2 = f(jnp.full((64,), 0.25), jnp.int32(4), jnp.int32(8),
               jax.random.key(0))
    assert float(o1) == 0.0 and float(o2) == 0.0


def test_dps_reduce_scatter_and_allgather_match_exact():
    """The two ZeRO half-collectives against numpy oracles on 8 ranks: the
    scattered mean lands within one grid step of the exact per-chunk mean,
    and the gathered params within one grid step of the shard values."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.fixed_point import FixedPointFormat
        from repro.dist.collectives import (dps_allgather_params,
                                            dps_reduce_scatter_mean,
                                            psum_stats)
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        fmt = FixedPointFormat.create(3, 5)
        n, per = 8, 1001                     # 1001 = 8*126 - 7: pad 7
        x = jax.random.normal(jax.random.key(0), (n, per)) * 0.5

        def body(xs, key):
            shard, stats = dps_reduce_scatter_mean(xs[0], fmt, "data", key)
            full, _ = dps_allgather_params(shard, fmt, "data",
                                           jax.random.fold_in(key, 1))
            gathered_shards = jax.lax.all_gather(shard, "data", axis=0,
                                                 tiled=True)
            return gathered_shards, full, psum_stats(stats, "data").count

        f = jax.jit(jax.shard_map(body, mesh=mesh,
                    in_specs=(P("data", None), P()),
                    out_specs=(P(), P(), P()), check_vma=False))
        shards, full, count = f(x, jax.random.key(42))

        chunk = -(-per // n)
        exact = np.zeros((n * chunk,))
        exact[:per] = np.asarray(x, np.float64).mean(0)
        # scatter leg: one stochastic encode per rank -> error < 2^-5
        err = np.abs(np.asarray(shards) - exact).max()
        assert err < 2.0 ** -5 + 1e-6, err
        # stats cover each global element exactly once
        assert float(count) == n * per, count
        # gather leg re-quantizes the shard once more -> within one more step
        err2 = np.abs(np.asarray(full) - np.asarray(shards)).max()
        assert err2 < 2.0 ** -5 + 1e-6, err2
        print("OK", err, err2)
    """)


def test_moe_a2a_matches_einsum_oracle():
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs.base import get_config, smoke
        from repro.dist.sharding import axis_rules, LogicalRules
        from repro.models import moe as moe_lib
        from repro.models.common import init_params
        from repro.dist.sharding import make_mesh

        cfg = dataclasses.replace(smoke(get_config('qwen3_moe_30b_a3b')),
                                  capacity_factor=8.0)  # no drops
        p = init_params(jax.random.key(0), moe_lib.moe_defs(cfg, jnp.float32))
        B, S, D = 4, 8, cfg.d_model
        x = jax.random.normal(jax.random.key(1), (B, S, D)) * 0.3

        # oracle: einsum path (no mesh)
        out_ref, aux_ref = jax.jit(
            lambda x: moe_lib.moe_apply(cfg, p, x))(x)

        mesh = make_mesh((2, 4), ("data", "model"))
        with mesh, axis_rules(mesh, LogicalRules()):
            out_a2a, aux_a2a = jax.jit(
                lambda x: moe_lib.moe_apply(cfg, p, x))(x)
        np.testing.assert_allclose(np.asarray(out_ref), np.asarray(out_a2a),
                                   atol=2e-5)
        np.testing.assert_allclose(float(aux_ref), float(aux_a2a), rtol=1e-5)
        print("OK")
    """)


def test_sharded_train_step_matches_single_device():
    """One quantized train step on a 2×4 mesh == the same step unsharded."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_config, smoke
        from repro.core import qtrain
        from repro.dist.sharding import axis_rules, LogicalRules
        from repro.launch import specs as specs_lib
        from repro.models import registry
        from repro.models.common import init_params
        from repro.optim import SGDConfig, make_optimizer
        from repro.dist.sharding import make_mesh

        cfg = smoke(get_config('llama3_2_3b'))
        mod = registry(cfg.family)
        qcfg = qtrain.QuantConfig(enabled=True)
        opt = make_optimizer(SGDConfig())
        step = specs_lib.build_train_step(cfg, qcfg, opt)
        params = init_params(jax.random.key(0), mod.model_defs(cfg))
        state = qtrain.TrainState.create(params, opt.init(params), qcfg,
                                         jax.random.key(1))
        batch = {"tokens": jax.random.randint(jax.random.key(2), (8, 17), 0,
                                              cfg.vocab)}
        _, m_ref = jax.jit(step)(state, batch)

        mesh = make_mesh((2, 4), ("data", "model"))
        rules = LogicalRules()
        sh = specs_lib.train_state_shardings(cfg, mesh, rules, opt, qcfg)
        bs = specs_lib.train_batch_shardings(
            cfg, type("S", (), {"batch": 8, "seq": 16})(), mesh, rules)
        with mesh, axis_rules(mesh, rules):
            state_s = jax.device_put(state, sh)
            batch_s = jax.device_put(batch, bs)
            _, m_sh = jax.jit(step, in_shardings=(sh, bs),
                              out_shardings=(sh, None))(state_s, batch_s)
        np.testing.assert_allclose(float(m_ref["loss"]), float(m_sh["loss"]),
                                   rtol=2e-4)
        print("OK loss", float(m_sh["loss"]))
    """)


def test_elastic_restore_across_meshes(tmp_path):
    """Checkpoint written from an 8-device run restores onto 1 device."""
    code = f"""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save
        from repro.dist.sharding import make_mesh
        mesh = make_mesh((8,), ("data",))
        x = jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                           NamedSharding(mesh, P("data", None)))
        save(r"{tmp_path}", 5, {{"x": x}})
        print("saved")
    """
    run_with_devices(code)
    # restore in THIS process (1 device)
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.checkpoint import restore
    restored, _ = restore(str(tmp_path), 5,
                          jax.eval_shape(lambda: {"x": jnp.zeros((8, 8))}))
    np.testing.assert_array_equal(np.asarray(restored["x"]),
                                  np.arange(64, dtype=np.float32).reshape(8, 8))
