"""Backward-overlapped bucketed wire (``repro.dist.overlap``).

Covers the ISSUE-7 acceptance criteria:
  (a) ``plan_buckets`` is a deterministic partition: contiguous
      leaf-index runs in backward (reverse-flatten) order, every leaf in
      exactly one bucket, non-divisible sizes included, and malformed
      plans are rejected at construction;
  (b) the bucketed collective is bit-exact against the monolithic
      ``dps_allreduce_mean_tree`` under round-to-nearest at pinned
      ⟨IL, FL⟩ — scalar AND per-leaf grouped formats — and its
      dispatch-leg stats are bit-exact under stochastic rounding too;
  (c) the overlapped train step (``QuantConfig(wire_overlap=True)``)
      matches the monolithic step bit-exactly at nearest, is a pure
      no-op without ``grad_allreduce_bits``, and composes with ZeRO-1
      through the group-aligned layout (the flow verifier proves the
      bucket schedule on the sharded halves too);
  (d) the precision-flow verifier proves PF-BUCKET-ENCODE /
      PF-BUCKET-DECODE on the real overlapped step and fires both on
      deliberately broken bucket schedules (double-encode, dropped
      leaf, mean-without-decode).

Multi-device tests run in a subprocess under
``xla_force_host_platform_device_count=8`` like tests/test_dist.py; the
plan units and flow oracles run in-process (no mesh needed).
"""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "src"))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


# ---------------------------------------------------------------------------
# BucketPlan units (pure Python — no devices).
# ---------------------------------------------------------------------------

LENET_SIZES = (48000, 1200, 30720, 120, 840, 10)


def test_plan_buckets_lenet_shape_and_determinism():
    from repro.dist import overlap

    plan = overlap.plan_buckets(LENET_SIZES, 1 << 16)
    # backward order: the tail leaves (materialized first) share a
    # bucket, the big first-layer leaf gets its own
    assert plan.buckets == ((1, 2, 3, 4, 5), (0,))
    assert plan.n_buckets == 2 and plan.n_leaves == len(LENET_SIZES)
    # deterministic: a static function of (sizes, target)
    assert overlap.plan_buckets(LENET_SIZES, 1 << 16) == plan
    assert plan.bucket_elems(0) == sum(LENET_SIZES) - 48000
    assert plan.bucket_elems(1) == 48000


def test_plan_buckets_partition_no_drops_no_dups():
    from repro.dist import overlap

    # awkward, non-divisible sizes (primes, singleton leaves)
    sizes = (7, 4097, 13, 1, 65536, 251, 3, 1023)
    for target in (1, 1000, 1 << 16, 1 << 30):
        plan = overlap.plan_buckets(sizes, target)
        seen = [g for b in plan.buckets for g in b]
        assert sorted(seen) == list(range(len(sizes)))   # partition
        assert len(seen) == len(set(seen))               # no dups
        for b, leaves in enumerate(plan.buckets):
            for g in leaves:
                assert plan.bucket_of(g) == b
    # a huge target degenerates to one bucket, a tiny one to per-leaf
    assert overlap.plan_buckets(sizes, 1 << 30).n_buckets == 1
    assert overlap.plan_buckets(sizes, 1).n_buckets == len(sizes)


def test_plan_validation_rejects_malformed():
    from repro.dist import overlap

    # not a partition (leaf 0 dropped)
    with pytest.raises(ValueError):
        overlap.BucketPlan(sizes=(4, 4), buckets=((1,),), target=8)
    # duplicate leaf
    with pytest.raises(ValueError):
        overlap.BucketPlan(sizes=(4, 4), buckets=((1,), (1, 0)), target=8)
    # forward (non-reverse) bucket order
    with pytest.raises(ValueError):
        overlap.BucketPlan(sizes=(4, 4), buckets=((0,), (1,)), target=8)


# ---------------------------------------------------------------------------
# Collective-level bit-exactness vs the monolithic pipeline (8 devices).
# ---------------------------------------------------------------------------

def test_bucketed_collective_bitexact_vs_monolithic():
    run_with_devices("""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.fixed_point import FixedPointFormat
        from repro.dist import collectives, overlap
        from repro.dist.sharding import make_mesh

        sizes = (48000, 1200, 30720, 120, 840, 10)
        plan = overlap.plan_buckets(sizes, 1 << 16)
        assert plan.n_buckets >= 2
        mesh = make_mesh((8,), ("data",))
        tree = {f"l{i}": jax.random.normal(
                    jax.random.fold_in(jax.random.key(0), i), (s,)) * 0.5
                for i, s in enumerate(sizes)}
        key = jax.random.key(7)
        sm = lambda f: jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=({k: P() for k in tree}, P()),
            out_specs=(P(), P()), check_vma=False))

        stat_fields = ("count", "nonzero", "overflow", "abs_err_sum",
                       "rel_err_sum", "abs_sum", "max_abs")
        for fmt, label in [
                (FixedPointFormat.create(3, 5), "scalar"),
                (FixedPointFormat(jnp.array([3, 2, 4, 3, 2, 3]),
                                  jnp.array([5, 6, 4, 5, 6, 5])), "grouped")]:
            def mono(tr, k, _f=fmt):
                return collectives.dps_allreduce_mean_tree(
                    tr, _f, "data", k, mode="nearest")
            def buck(tr, k, _f=fmt):
                return overlap.bucketed_allreduce_mean_tree(
                    tr, _f, "data", k, mode="nearest", plan=plan)
            m1, s1 = sm(mono)(tree, key)
            m2, s2 = sm(buck)(tree, key)
            for k2 in tree:
                assert np.array_equal(np.asarray(m1[k2]),
                                      np.asarray(m2[k2])), (label, k2)
            for f in stat_fields:
                assert np.array_equal(np.asarray(getattr(s1, f)),
                                      np.asarray(getattr(s2, f))), (label, f)

        # stochastic rounding: the dispatch-leg stats (what steers the
        # wire controller) stay bit-exact — leg-1 rounding bits are keyed
        # per GLOBAL leaf index, identically to the monolithic pipeline
        fmt = FixedPointFormat.create(3, 5)
        def monoS(tr, k):
            return collectives.dps_allreduce_mean_tree(
                tr, fmt, "data", k, mode="stochastic")
        def buckS(tr, k):
            return overlap.bucketed_allreduce_mean_tree(
                tr, fmt, "data", k, mode="stochastic", plan=plan)
        _, s1 = sm(monoS)(tree, key)
        _, s2 = sm(buckS)(tree, key)
        for f in ("count", "nonzero", "overflow", "abs_err_sum",
                  "abs_sum", "max_abs"):
            assert np.array_equal(np.asarray(getattr(s1, f)),
                                  np.asarray(getattr(s2, f))), f
        print("OK")
        """)


# ---------------------------------------------------------------------------
# Train-step parity + flow verification + ZeRO rejection (8 devices).
# ---------------------------------------------------------------------------

def test_overlap_step_bitexact_and_flow_clean():
    run_with_devices("""
        import dataclasses
        import jax
        import jax.numpy as jnp
        from repro.analysis import flow
        from repro.core import qtrain
        from repro.core.dps import DPSHyper
        from repro.models import lenet
        from repro.optim import SGDConfig, make_optimizer
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        base = dict(enabled=False, controller="static",
                    hyper_grads=DPSHyper(il_init=6, fl_init=2),
                    rounding="nearest", grad_allreduce_bits=8)
        qA = qtrain.QuantConfig(**base)
        qB = qtrain.QuantConfig(**base, wire_overlap=True,
                                wire_bucket_elems=1 << 15)
        opt = make_optimizer(SGDConfig())
        params = lenet.init(jax.random.key(0))
        batch = {"images": jax.random.normal(jax.random.key(2),
                                             (64, 28, 28, 1)) * 0.5,
                 "labels": jax.random.randint(jax.random.key(3), (64,),
                                              0, 10)}

        def run(q):
            st = qtrain.TrainState.create(params, opt.init(params), q,
                                          jax.random.key(1))
            step = qtrain.make_train_step(lenet.loss_fn, opt, q, mesh=mesh)
            return step, jax.jit(step)(st, batch)

        # scalar wire format: overlapped step bit-exact vs monolithic
        stepA, (sA, mA) = run(qA)
        stepB, (sB, mB) = run(qB)
        assert stepA.wire_sync_active and not stepA.wire_overlap_active
        assert stepB.wire_sync_active and stepB.wire_overlap_active
        assert float(mA["loss"]) == float(mB["loss"])
        assert float(mA["E_wire"]) == float(mB["E_wire"])
        for a, b in zip(jax.tree.leaves(sA.params), jax.tree.leaves(sB.params)):
            assert jnp.array_equal(a, b), "overlap must be bit-exact"

        # per-layer grouped wire formats too
        qAg, qBg = qA.with_per_layer_wire(params), qB.with_per_layer_wire(params)
        _, (sA, mA) = run(qAg)
        _, (sB, mB) = run(qBg)
        for a, b in zip(jax.tree.leaves(sA.params), jax.tree.leaves(sB.params)):
            assert jnp.array_equal(a, b), "grouped overlap must be bit-exact"

        # the flow verifier proves the bucket schedule on the REAL step
        st = qtrain.TrainState.create(params, opt.init(params), qBg,
                                      jax.random.key(1))
        step = qtrain.make_train_step(lenet.loss_fn, opt, qBg, mesh=mesh)
        r = flow.analyze_fn(step, st, batch, name="overlap-step")
        assert r.ok, r.summary()
        assert "PF-BUCKET-ENCODE" in r.checked
        assert "PF-BUCKET-DECODE" in r.checked

        # ZeRO-1 composes: the group-aligned layout keeps the leaf
        # boundaries buckets are made of, and the verifier proves the
        # same bucket schedule on the SHARDED reduce-scatter half
        qZ = dataclasses.replace(qBg, zero_opt_shards=8)
        stepZ = qtrain.make_train_step(lenet.loss_fn, opt, qZ, mesh=mesh)
        assert stepZ.zero_opt_active and stepZ.wire_overlap_active
        assert stepZ.zero_groupaligned_active
        stZ = qtrain.TrainState.create(
            params, qtrain.zero_opt_state(opt, params, 8, qcfg=qZ), qZ,
            jax.random.key(1))
        _, mZ = jax.jit(stepZ)(stZ, batch)
        assert float(mZ["loss"]) == float(mA["loss"])
        r = flow.analyze_fn(stepZ, stZ, batch, name="zero-overlap-step")
        assert r.ok, r.summary()
        assert "PF-BUCKET-ENCODE" in r.checked
        assert "PF-BUCKET-DECODE" in r.checked
        print("OK")
        """)


def test_bucketed_bitexact_both_modes():
    """The PR-7 SR caveat is gone: bucketed decoded means AND stats are
    bit-exact vs the monolithic collective under BOTH rounding modes —
    every rounding-bit draw (dispatch and gather leg) is keyed by global
    leaf index, so the bucket partition cannot move it."""
    run_with_devices("""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core.fixed_point import FixedPointFormat
        from repro.dist import collectives, overlap
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        tree = {"a": jax.random.normal(jax.random.key(0), (8, 37, 5)) * .2,
                "b": jax.random.normal(jax.random.key(1), (8, 3)) * .1,
                "c": jax.random.normal(jax.random.key(2), (8, 300)) * .3,
                "d": jax.random.normal(jax.random.key(3), (8, 1000)) * .05}
        fmts = {
            "grouped": FixedPointFormat(jnp.full((4,), 3, jnp.int32),
                                        jnp.full((4,), 5, jnp.int32)),
            "scalar": FixedPointFormat.create(3, 5)}
        key = jax.random.key(7)
        for label, fmt in fmts.items():
            for mode in ("nearest", "stochastic"):
                def mono(t, _f=fmt, _m=mode):
                    return collectives.dps_allreduce_mean_tree(
                        t, _f, "data", key, mode=_m)
                def buck(t, _f=fmt, _m=mode):
                    return overlap.bucketed_allreduce_mean_tree(
                        t, _f, "data", key, mode=_m, target_elems=512)
                sm = lambda f: jax.jit(jax.shard_map(
                    f, mesh=mesh, in_specs=(P("data"),),
                    out_specs=(P(), P()), check_vma=False))
                m, s1 = sm(mono)(tree)
                b, s2 = sm(buck)(tree)
                for x, y in zip(jax.tree.leaves(m), jax.tree.leaves(b)):
                    assert jnp.array_equal(x, y), (label, mode)
                for x, y in zip(jax.tree.leaves(s1), jax.tree.leaves(s2)):
                    assert jnp.array_equal(x, y), (label, mode, "stats")
        print("OK")
        """)


def test_wire_overlap_without_bits_is_noop():
    run_with_devices("""
        import jax
        import jax.numpy as jnp
        from repro.core import qtrain
        from repro.models import lenet
        from repro.optim import SGDConfig, make_optimizer
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        # wire_overlap without grad_allreduce_bits: no wire, no buckets —
        # the step must match the meshless reference bit-exactly
        qcfg = qtrain.QuantConfig(enabled=True, wire_overlap=True)
        opt = make_optimizer(SGDConfig())
        params = lenet.init(jax.random.key(0))
        batch = {"images": jax.random.normal(jax.random.key(2),
                                             (64, 28, 28, 1)),
                 "labels": jax.random.randint(jax.random.key(3), (64,),
                                              0, 10)}
        st = qtrain.TrainState.create(params, opt.init(params), qcfg,
                                      jax.random.key(1))
        step_ref = qtrain.make_train_step(lenet.loss_fn, opt, qcfg)
        step_mesh = qtrain.make_train_step(lenet.loss_fn, opt, qcfg,
                                           mesh=mesh)
        assert not step_mesh.wire_sync_active
        assert not step_mesh.wire_overlap_active
        s1, m1 = jax.jit(step_ref)(st, batch)
        s2, m2 = jax.jit(step_mesh)(st, batch)
        assert float(m1["loss"]) == float(m2["loss"])
        for a, b in zip(jax.tree.leaves(s1.params), jax.tree.leaves(s2.params)):
            assert jnp.array_equal(a, b)
        print("OK")
        """)


# ---------------------------------------------------------------------------
# Flow oracles: the PF-BUCKET rules fire on deliberately broken schedules
# (in-process; the analyzer traces, nothing executes on a mesh).
# ---------------------------------------------------------------------------

def _fmt():
    from repro.core.fixed_point import FixedPointFormat
    return FixedPointFormat.create(3, 5)


def test_oracle_double_encoded_bucket_fires():
    import jax
    import jax.numpy as jnp
    from repro.analysis import flow
    from repro.core import tagging
    from repro.dist import collectives

    fmt = _fmt()

    def double_encode(x, k):
        r = tagging.tag(x, "wire_bucket", stage="ready", bucket=0, leaf=0,
                        n=1)
        w1, _ = collectives.wire_encode(r.reshape(-1), fmt, key=k,
                                        mode="nearest")
        w2, _ = collectives.wire_encode(r.reshape(-1), fmt, key=k,
                                        mode="nearest")
        return w1, w2

    r = flow.analyze_fn(double_encode, jnp.zeros((64,)), jax.random.key(0))
    assert "PF-BUCKET-ENCODE" in r.rules_fired()


def test_oracle_dropped_bucket_fires():
    import jax.numpy as jnp
    from repro.analysis import flow
    from repro.core import tagging
    from repro.dist import collectives

    fmt = _fmt()

    def dropped(x):
        # declares n=2 buckets but only bucket 0 ever reaches the wire
        r0 = tagging.tag(x, "wire_bucket", stage="ready", bucket=0, leaf=0,
                         n=2)
        w, _ = collectives.wire_encode(r0.reshape(-1), fmt, key=None,
                                       mode="nearest")
        return tagging.tag(collectives.wire_decode(w, fmt), "wire_bucket",
                           stage="mean", bucket=0, n=2)

    r = flow.analyze_fn(dropped, jnp.zeros((64,)))
    assert "PF-BUCKET-ENCODE" in r.rules_fired()


def test_oracle_mean_without_decode_fires():
    import jax.numpy as jnp
    from repro.analysis import flow
    from repro.core import tagging
    from repro.dist import collectives

    fmt = _fmt()

    def no_decode(x):
        r0 = tagging.tag(x, "wire_bucket", stage="ready", bucket=0, leaf=0,
                         n=1)
        w, _ = collectives.wire_encode(r0.reshape(-1), fmt, key=None,
                                       mode="nearest")
        # arithmetic between decode and the mean tag kills the taint
        return tagging.tag(w.astype(jnp.float32) * 2.0, "wire_bucket",
                           stage="mean", bucket=0, n=1)

    r = flow.analyze_fn(no_decode, jnp.zeros((64,)))
    assert "PF-BUCKET-DECODE" in r.rules_fired()


def test_oracle_clean_bucketed_pipeline_checks_rules():
    """The closest correct variant stays quiet — and marks both bucket
    rules checked (not vacuous) on a genuinely bucketed pipeline."""
    import jax
    import jax.numpy as jnp
    from repro.analysis import flow
    from repro.dist import overlap

    sizes = (640, 96, 32)
    plan = overlap.plan_buckets(sizes, 128)
    tree = {f"l{i}": jnp.ones((s,)) for i, s in enumerate(sizes)}

    def step(tr, k):
        return overlap.bucketed_allreduce_mean_tree(
            tr, _fmt(), "data", k, mode="nearest", plan=plan)

    r = flow.analyze_fn(step, tree, jax.random.key(0),
                        axis_env=[("data", 8)])
    assert r.ok, r.summary()
    assert "PF-BUCKET-ENCODE" in r.checked
    assert "PF-BUCKET-DECODE" in r.checked
