"""Group-aligned ZeRO layout (``repro.dist.sharding.GroupAlignedPartitioner``)
and its composition with per-layer wire formats and the overlapped bucketed
pipeline (ISSUE-8).

Covers the acceptance criteria:
  (a) partitioner edge cases — non-divisible leaves (the 37/8 case), leaves
      smaller than one quantum, a single-leaf tree — every leaf slot starts
      on a quantum boundary, rank chunks never straddle a leaf, and
      flatten → shard → assemble → unflatten round-trips bit-exactly;
  (b) ``zero_opt_shards`` + per-layer ``wire_grads`` + ``wire_overlap``
      runs end-to-end on an 8-device host mesh with no rejection branch,
      and is bit-exact vs the replicated per-layer step over 3 steps with
      live DPS controllers — at ``bits=None`` (pure layout change) and at
      8 wire bits under BOTH nearest and stochastic rounding (every wire
      rounding-bit draw is keyed by global leaf index, so the sharded and
      replicated schedules consume identical bit streams);
  (c) engagement policy — mismatched ``zero_opt_shards`` warns and falls
      back (no raise), and the chosen paths surface as ``train_step``
      attributes including ``zero_groupaligned_active``.

The parity tests run with a policy-excluded norm-scale leaf: the flat wire
legs cannot honor per-leaf carve-outs, so the params all-gather stays fp32
(``full_quant=False``) — the regime where the replicated and sharded steps
are defined to coincide exactly (the params-leg int8 snap is an extra
quantization the replicated step never performs).  Power-of-two SGD hypers
keep the shard-local optimizer math FMA-contraction-proof (see
``SGD._leaf``).
"""

import os
import subprocess
import sys
import textwrap

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
# Partitioner geometry + round-trips (in-process, no mesh needed).
# ---------------------------------------------------------------------------

def _roundtrip(tree, n_shards, **kw):
    import jax.numpy as jnp
    import numpy as np
    from repro.dist.sharding import GroupAlignedPartitioner

    part = GroupAlignedPartitioner.create(tree, n_shards, **kw)
    # geometry invariants: aligned leaf slots, whole-quantum rank chunks
    assert part.padded_size == n_shards * part.shard_size
    for b, lay in enumerate(part.layouts):
        assert lay.chunk % lay.quantum == 0
        assert part.bucket_offset(b) % lay.quantum == 0
    import jax
    leaves = jax.tree_util.tree_leaves(tree)
    for g in range(len(leaves)):
        # every leaf slot starts on its bucket's quantum boundary
        b = next(i for i, r in enumerate(part.buckets) if g in r)
        off = part.leaf_offset(g) - part.bucket_offset(b)
        assert off % part.layouts[b].quantum == 0, (g, off)

    flat = part.flatten(tree)
    assert flat.shape == (part.padded_size,) and flat.dtype == jnp.float32
    back = part.unflatten(flat)
    for a, c in zip(leaves, jax.tree_util.tree_leaves(back)):
        assert c.dtype == a.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(c, np.float32))
    # shard/assemble round-trip: rank chunks tile the flat layout exactly
    gathered = jnp.stack([part.shard(flat, r) for r in range(n_shards)])
    np.testing.assert_array_equal(np.asarray(part.assemble(gathered)),
                                  np.asarray(flat))
    return part


def test_groupaligned_non_divisible_37_over_8():
    import jax.numpy as jnp

    tree = {"w": jnp.arange(37.0) / 64}
    part = _roundtrip(tree, 8)
    assert part.size == 37
    # one leaf, one bucket; the slot pads to the quantum and the chunk
    # divides it evenly across 8 ranks
    assert part.n_buckets == 1
    assert part.padded_size >= 40          # at least the plain layout's pad


def test_groupaligned_leaves_smaller_than_quantum():
    import jax.numpy as jnp

    # every leaf far below one quantum: each still gets its own aligned
    # slot, so per-leaf formats survive and chunks never straddle leaves
    tree = {"a": jnp.ones((3,)), "b": jnp.ones((5, 1)),
            "c": jnp.ones((7,)), "d": jnp.ones(()) * 2}
    part = _roundtrip(tree, 8)
    assert part.size == 3 + 5 + 7 + 1
    offs = [part.leaf_offset(g) for g in range(4)]
    assert offs == sorted(offs) and len(set(offs)) == 4


def test_groupaligned_single_leaf_tree():
    import jax.numpy as jnp

    part = _roundtrip({"only": jnp.arange(1000.0).reshape(10, 100)}, 8)
    assert part.n_buckets == 1 and part.size == 1000


def test_groupaligned_bucketed_runs():
    import jax.numpy as jnp

    tree = {f"l{i}": jnp.ones((s,)) * i
            for i, s in enumerate((640, 96, 32, 7))}
    part = _roundtrip(tree, 8, buckets=((0,), (1, 2), (3,)))
    assert part.n_buckets == 3
    assert part.leaf_range(1) == (1, 3)
    # bucket offsets are whole quanta and shard offsets tile the chunk
    assert part.shard_offset(0) == 0
    assert part.shard_offset(2) == sum(l.chunk for l in part.layouts[:2])


def test_groupaligned_rejects_malformed_buckets():
    import jax.numpy as jnp
    import pytest
    from repro.dist.sharding import GroupAlignedPartitioner

    tree = {"a": jnp.ones((4,)), "b": jnp.ones((4,))}
    with pytest.raises(ValueError):     # leaf 1 dropped
        GroupAlignedPartitioner.create(tree, 4, buckets=((0,),))
    with pytest.raises(ValueError):     # duplicate leaf
        GroupAlignedPartitioner.create(tree, 4, buckets=((0,), (0, 1)))


# ---------------------------------------------------------------------------
# Train-step parity on 8 host devices.
# ---------------------------------------------------------------------------

_PARITY_PRELUDE = """
    import warnings
    import jax
    import jax.numpy as jnp
    from repro.core import qtrain
    from repro.models.common import rms_norm
    from repro.optim import SGDConfig, make_optimizer
    from repro.dist.sharding import make_mesh

    def loss_fn(params, batch, qctx=None):
        h = rms_norm(batch["x"] @ params["w1"], params["norm_scale"])
        return jnp.mean((h @ params["w2"] - batch["y"]) ** 2), {}

    # norm_scale is policy-excluded -> the params all-gather stays fp32
    # (full_quant=False), the regime where sharded == replicated exactly;
    # w1 is 16x37 so the flat slot is the non-divisible 592/8 case
    params = {"w1": jax.random.normal(jax.random.key(0), (16, 37)) * 0.3,
              "norm_scale": jnp.ones((37,)),
              "w2": jax.random.normal(jax.random.key(4), (37, 8)) * 0.3}
    batch = {"x": jax.random.normal(jax.random.key(1), (32, 16)),
             "y": jax.random.normal(jax.random.key(2), (32, 8))}
    mesh = make_mesh((8,), ("data",))
    # power-of-two hypers: shard-local SGD math is FMA-contraction-proof
    opt = make_optimizer(SGDConfig(lr=0.0078125, momentum=0.5,
                                   weight_decay=0.00048828125,
                                   schedule="const"))

    def run_pair(qr, qz, steps=3):
        step_r = qtrain.make_train_step(loss_fn, opt, qr, mesh=mesh)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            step_z = qtrain.make_train_step(loss_fn, opt, qz, mesh=mesh)
        s_r = qtrain.TrainState.create(params, opt.init(params), qr,
                                       jax.random.key(3))
        s_z = qtrain.TrainState.create(
            params, qtrain.zero_opt_state(opt, params, 8, qcfg=qz), qz,
            jax.random.key(3))
        jr, jz = jax.jit(step_r), jax.jit(step_z)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            for i in range(steps):
                s_r, m_r = jr(s_r, batch)
                s_z, m_z = jz(s_z, batch)
                assert float(m_r["loss"]) == float(m_z["loss"]), i
        for k in params:
            assert jnp.array_equal(s_r.params[k], s_z.params[k]), k
        # live DPS controllers must have seen identical stats streams
        for a, b in zip(jax.tree.leaves(s_r.dps), jax.tree.leaves(s_z.dps)):
            assert jnp.array_equal(a, b), "DPS trajectories must match"
        return step_z
"""


def test_zero_groupalign_parity_bits_none():
    """bits=None: ZeRO + overlap flags degrade to the plain layout and the
    step is a pure layout change — bit-exact with the replicated step."""
    run_with_devices(_PARITY_PRELUDE + """
    qr = qtrain.QuantConfig(enabled=True)
    qz = qtrain.QuantConfig(enabled=True, zero_opt_shards=8,
                            wire_overlap=True)
    step_z = run_pair(qr, qz)
    assert step_z.zero_opt_active
    assert not step_z.wire_sync_active
    assert not step_z.zero_groupaligned_active   # no wire, plain layout
    print("OK")
    """)


def test_zero_groupalign_parity_wire8_both_modes():
    """8 wire bits, ZeRO + per-layer + overlap vs replicated per-layer:
    bit-exact over 3 steps with live DPS controllers under nearest AND
    stochastic rounding (global-leaf-indexed wire bit draws)."""
    run_with_devices(_PARITY_PRELUDE + """
    for mode in ("nearest", "stochastic"):
        base = dict(enabled=True, rounding=mode, grad_allreduce_bits=8)
        qr = qtrain.QuantConfig(**base).with_per_layer_wire(params)
        qz = qtrain.QuantConfig(**base, zero_opt_shards=8,
                                wire_overlap=True).with_per_layer_wire(params)
        step_z = run_pair(qr, qz)
        assert step_z.zero_opt_active and step_z.wire_sync_active
        assert step_z.wire_overlap_active and step_z.zero_groupaligned_active
        print("OK", mode)
    """)


def test_zero_groupalign_per_layer_without_overlap():
    """Per-layer wire under ZeRO without bucketing: the single-bucket
    aligned layout still routes both halves through the grouped codec."""
    run_with_devices(_PARITY_PRELUDE + """
    base = dict(enabled=True, rounding="nearest", grad_allreduce_bits=8)
    qr = qtrain.QuantConfig(**base).with_per_layer_wire(params)
    qz = qtrain.QuantConfig(**base,
                            zero_opt_shards=8).with_per_layer_wire(params)
    step_z = run_pair(qr, qz)
    assert step_z.zero_groupaligned_active
    assert not step_z.wire_overlap_active
    print("OK")
    """)


def test_zero_shards_mismatch_warns_and_falls_back():
    """Engagement-mismatch policy: zero_opt_shards != the mesh's data axis
    warns and runs the replicated optimizer state (no raise)."""
    run_with_devices("""
        import warnings
        import jax
        import jax.numpy as jnp
        from repro.core import qtrain
        from repro.models import lenet
        from repro.optim import SGDConfig, make_optimizer
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        qcfg = qtrain.QuantConfig(enabled=True, zero_opt_shards=4)
        assert not qtrain.zero_opt_engaged(qcfg, mesh)
        opt = make_optimizer(SGDConfig())
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            step = qtrain.make_train_step(lenet.loss_fn, opt, qcfg,
                                          mesh=mesh)
        assert any("does not match" in str(x.message) for x in w)
        assert not step.zero_opt_active
        assert not step.zero_groupaligned_active
        params = lenet.init(jax.random.key(0))
        batch = {"images": jnp.zeros((64, 28, 28, 1)),
                 "labels": jnp.zeros((64,), jnp.int32)}
        st = qtrain.TrainState.create(params, opt.init(params), qcfg,
                                      jax.random.key(1))
        jax.jit(step)(st, batch)      # replicated fallback runs
        print("OK")
        """)


def test_zero_groupalign_opt_state_layout_matches_step():
    """zero_opt_state(qcfg=...) sizes the flat state for the SAME layout
    the step shards over — the aligned padded size, not the plain one."""
    run_with_devices("""
        import jax
        import jax.numpy as jnp
        from repro.core import qtrain
        from repro.dist.sharding import GroupAlignedPartitioner, \\
            ZeroPartitioner
        from repro.models import lenet
        from repro.optim import SGDConfig, make_optimizer

        opt = make_optimizer(SGDConfig())
        params = lenet.init(jax.random.key(0))
        qz = qtrain.QuantConfig(enabled=True, grad_allreduce_bits=8,
                                zero_opt_shards=8,
                                wire_overlap=True).with_per_layer_wire(params)
        part = qtrain.zero_partitioner(qz, params, 8)
        assert isinstance(part, GroupAlignedPartitioner)
        st = qtrain.zero_opt_state(opt, params, 8, qcfg=qz)
        assert st["mu"].shape == (part.padded_size,)
        # legacy default (no qcfg): the plain layout, unchanged
        plain = ZeroPartitioner.create(params, 8)
        st0 = qtrain.zero_opt_state(opt, params, 8)
        assert st0["mu"].shape == (plain.padded_size,)
        # scalar wire without overlap keeps the plain layout too
        qs = qtrain.QuantConfig(enabled=True, grad_allreduce_bits=8,
                                zero_opt_shards=8)
        assert isinstance(qtrain.zero_partitioner(qs, params, 8),
                          ZeroPartitioner)
        print("OK")
        """)
