"""ZeRO-1 sharded-optimizer regression tests (``QuantConfig.zero_opt_shards``):
run in a subprocess under ``xla_force_host_platform_device_count=8`` like
tests/test_dist.py.

Covers the ISSUE-3 acceptance criteria:
  (a) ``zero_opt_shards=8`` + ``bits=None`` is bit-exact with the replicated
      ``make_train_step`` over multiple steps (params, optimizer state, loss
      and DPS trajectories) — with power-of-two SGD hypers, the regime where
      the shard-local optimizer math is FMA-contraction-proof (see
      ``SGD._leaf``),
  (b) the fused ZeRO+int8-wire step's single SGD update stays within the
      two wire grid steps the two compressed legs can add,
  (c) the int8 reduce-scatter + all-gather schedule moves ≤ ~1/4 the wire
      bytes of an fp32 reduce-scatter + all-gather (ring model, both sides
      parsed from compiled HLO via ``hlo_stats.collective_wire_bytes``),
  (d) the ZeroPartitioner's padded flat layout round-trips non-divisible
      leaves through a real scatter/step/gather cycle on an 8-rank mesh.

``REPRO_WIRE_CONTROLLER`` pins the wire domains' controller kind for the
fused wire tests (CI's dist-wire-ctrl leg sets ``flexpoint``); the wire
formats they assert on are initial-step formats fixed by ``wire_hyper``'s
``il_init``, so any kind satisfies them.
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_zero_bits_none_bitexact_with_replicated_step():
    """(a): the flat-sharded optimizer is a pure layout change at bits=None."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import qtrain
        from repro.dist.sharding import ZeroPartitioner
        from repro.models import lenet
        from repro.optim import SGDConfig, make_optimizer
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        # power-of-two lr/momentum/weight_decay: every scalar product in
        # the SGD leaf is exact in f32, so LLVM's layout-dependent FMA
        # contraction cannot make the per-leaf and flat-shard updates
        # differ (the documented bit-exactness regime).
        cfg = SGDConfig(lr=0.0078125, momentum=0.5,
                        weight_decay=0.00048828125, schedule="const")
        opt = make_optimizer(cfg)
        qcfg0 = qtrain.QuantConfig(enabled=True)
        qcfgz = qtrain.QuantConfig(enabled=True, zero_opt_shards=8)
        params = lenet.init(jax.random.key(0))
        batch = {"images": jax.random.normal(jax.random.key(2),
                                             (64, 28, 28, 1)),
                 "labels": jax.random.randint(jax.random.key(3), (64,),
                                              0, 10)}

        step_ref = qtrain.make_train_step(lenet.loss_fn, opt, qcfg0)
        step_zero = qtrain.make_train_step(lenet.loss_fn, opt, qcfgz,
                                           mesh=mesh)
        assert step_zero.zero_opt_active and not step_zero.wire_sync_active
        s_r = qtrain.TrainState.create(params, opt.init(params), qcfg0,
                                       jax.random.key(1))
        s_z = qtrain.TrainState.create(
            params, qtrain.zero_opt_state(opt, params, 8), qcfgz,
            jax.random.key(1))
        # the ZeRO state is 1/8 per device: one flat padded vector
        part = ZeroPartitioner.create(params, 8)
        assert s_z.opt_state["mu"].shape == (part.padded_size,)

        jr, jz = jax.jit(step_ref), jax.jit(step_zero)
        for i in range(3):
            s_r, m_r = jr(s_r, batch)
            s_z, m_z = jz(s_z, batch)
            assert float(m_r["loss"]) == float(m_z["loss"]), i
        for a, b in zip(jax.tree.leaves(s_r.params),
                        jax.tree.leaves(s_z.params)):
            assert jnp.array_equal(a, b), "params must be bit-exact"
        np.testing.assert_array_equal(
            np.asarray(part.flatten(s_r.opt_state["mu"])),
            np.asarray(s_z.opt_state["mu"]))
        for a, b in zip(jax.tree.leaves(s_r.dps), jax.tree.leaves(s_z.dps)):
            assert jnp.array_equal(a, b), "DPS trajectories must match"
        print("OK")
    """)


def test_zero_wire8_update_within_two_grid_steps():
    """(b): fp32 training + int8 wire only — the fused step's two wire legs
    (grads reduce-scatter on the ⟨6,2⟩ grid, params all-gather on the ⟨2,6⟩
    grid) bound the parameter perturbation element-wise."""
    run_with_devices("""
        import os
        import jax, jax.numpy as jnp
        from repro.core import qtrain
        from repro.core.dps import DPSHyper
        from repro.models import lenet
        from repro.optim import SGDConfig, make_optimizer
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        # static compute formats: grads <6,2> (range +-32 covers init
        # grads), weights <2,14>; the wire domains' initial formats are
        # <6,2> / <2,6> from wire_hyper's il_init regardless of kind
        # (the subprocess inherits REPRO_WIRE_CONTROLLER from CI)
        base = dict(enabled=False, controller="static",
                    hyper_grads=DPSHyper(il_init=6, fl_init=2),
                    hyper_weights=DPSHyper(il_init=2, fl_init=14),
                    wire_controller=os.environ.get("REPRO_WIRE_CONTROLLER")
                    or "flexpoint")
        qcfg0 = qtrain.QuantConfig(**base)
        qcfgz = qtrain.QuantConfig(**base, grad_allreduce_bits=8,
                                   zero_opt_shards=8)
        opt = make_optimizer(SGDConfig())
        # params inside the params-leg range (<2, 6> holds |w| <= 127/64):
        # the bound is on rounding error, clipping is overflow
        params = jax.tree.map(lambda w: jnp.clip(w, -1.9, 1.9),
                              lenet.init(jax.random.key(0)))
        batch = {"images": jax.random.normal(jax.random.key(2),
                                             (64, 28, 28, 1)) * 0.5,
                 "labels": jax.random.randint(jax.random.key(3), (64,),
                                              0, 10)}

        s0, _ = jax.jit(qtrain.make_train_step(lenet.loss_fn, opt, qcfg0))(
            qtrain.TrainState.create(params, opt.init(params), qcfg0,
                                     jax.random.key(1)), batch)
        stepz = qtrain.make_train_step(lenet.loss_fn, opt, qcfgz, mesh=mesh)
        assert stepz.zero_opt_active and stepz.wire_sync_active
        sz = qtrain.TrainState.create(
            params, qtrain.zero_opt_state(opt, params, 8), qcfgz,
            jax.random.key(1))
        sz, mz = jax.jit(stepz)(sz, batch)

        assert float(mz["R_wire"]) == 0.0, "both legs must fit their ranges"
        assert float(mz["E_wire"]) > 0.0, "wire stats must be live"
        # one stochastic encode per leg: < 1 grads grid step through the
        # reduce-scatter mean (lr-scaled by the optimizer) + < 1 params
        # grid step through the all-gather.
        lr = 0.01                  # SGDConfig default, momentum step 1
        bound = lr * 2 * 2.0 ** -2 + 2 * 2.0 ** -6
        diff = max(float(jnp.abs(a - b).max()) for a, b in
                   zip(jax.tree.leaves(s0.params), jax.tree.leaves(sz.params)))
        assert diff <= bound, (diff, bound)
        print("OK diff", diff, "bound", bound)
    """)


def test_zero_wire_bytes_le_quarter_fp32_reduce_scatter():
    """(c): the acceptance wire-byte criterion, measured HLO vs measured HLO."""
    run_with_devices("""
        import os
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.core import qtrain
        from repro.core.dps import DPSHyper
        from repro.launch.hlo_stats import collective_wire_bytes
        from repro.models import lenet
        from repro.optim import SGDConfig, make_optimizer
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        qcfgz = qtrain.QuantConfig(enabled=False, controller="static",
                                   hyper_grads=DPSHyper(il_init=6, fl_init=2),
                                   grad_allreduce_bits=8, zero_opt_shards=8,
                                   wire_controller=os.environ.get(
                                       "REPRO_WIRE_CONTROLLER")
                                   or "flexpoint")
        opt = make_optimizer(SGDConfig())
        params = lenet.init(jax.random.key(0))
        batch = {"images": jnp.zeros((64, 28, 28, 1)),
                 "labels": jnp.zeros((64,), jnp.int32)}
        sz = qtrain.TrainState.create(
            params, qtrain.zero_opt_state(opt, params, 8), qcfgz,
            jax.random.key(1))
        jz = jax.jit(qtrain.make_train_step(lenet.loss_fn, opt, qcfgz,
                                            mesh=mesh))
        wz = collective_wire_bytes(jz.lower(sz, batch).compile().as_text())

        # fp32 baseline: the same two-leg schedule (reduce-scatter +
        # all-gather) without the codec, over the same padded flat size.
        n_params = sum(p.size for p in jax.tree.leaves(params))
        chunk = -(-n_params // 8)
        def ref(x):
            s = jax.lax.psum_scatter(x.reshape(8, chunk), "data",
                                     scatter_dimension=0, tiled=True)
            return jax.lax.all_gather(s, "data", axis=0, tiled=True)
        fr = jax.jit(jax.shard_map(ref, mesh=mesh, in_specs=P(),
                                   out_specs=P(), check_vma=False))
        wr = collective_wire_bytes(
            fr.lower(jax.ShapeDtypeStruct((8 * chunk,), jnp.float32)
                     ).compile().as_text())

        f32_ref = wr["total"]
        # both fp32 legs must be present and full-sized (2 x 4 x padded)
        assert f32_ref >= 2 * 4 * 8 * chunk * 0.9, wr
        s8 = wz["by_dtype"].get("s8", 0.0)
        assert s8 > 0.0, wz
        assert s8 <= 0.26 * f32_ref, (s8, f32_ref)
        # residual f32 collectives in the ZeRO step are stats/loss scalars
        assert wz["by_dtype"].get("f32", 0.0) < 0.01 * f32_ref, wz
        print("OK ratio", s8 / f32_ref)
    """)


def test_zero_wire_respects_policy_excluded_leaves():
    """The flat layout can't skip policy-excluded leaves per-element, so a
    tree containing one (e.g. a norm scale) must warn, gather params in
    fp32, and never snap the excluded leaf's VALUE onto the coarse wire
    grid — while the gradient scatter leg stays int8."""
    run_with_devices("""
        import warnings
        import jax, jax.numpy as jnp
        from repro.core import qtrain
        from repro.core.dps import DPSHyper
        from repro.models.common import rms_norm
        from repro.optim import SGDConfig, make_optimizer
        from repro.dist.sharding import make_mesh

        def loss_fn(params, batch, qctx=None):
            h = rms_norm(batch["x"] @ params["w"], params["out_norm_scale"])
            return jnp.mean((h - batch["y"]) ** 2), {}

        params = {"w": jax.random.normal(jax.random.key(0), (16, 16)) * 0.3,
                  "out_norm_scale": jnp.ones((16,))}
        batch = {"x": jax.random.normal(jax.random.key(1), (32, 16)),
                 "y": jax.random.normal(jax.random.key(2), (32, 16))}

        mesh = make_mesh((8,), ("data",))
        qcfg = qtrain.QuantConfig(enabled=True,
                                  hyper_weights=DPSHyper(il_init=2,
                                                         fl_init=14),
                                  grad_allreduce_bits=8, zero_opt_shards=8)
        opt = make_optimizer(SGDConfig())
        step = qtrain.make_train_step(loss_fn, opt, qcfg, mesh=mesh)
        state = qtrain.TrainState.create(
            params, qtrain.zero_opt_state(opt, params, 8), qcfg,
            jax.random.key(3))
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            jitted = jax.jit(step)
            s1, m = jitted(state, batch)
        assert any("cannot skip them per-leaf" in str(x.message) for x in w)
        # params leg fp32 => zero params-leg wire stats merged in; the
        # grads scatter leg is still live int8
        assert float(m["E_wire"]) > 0.0
        hlo = jitted.lower(state, batch).compile().as_text()
        lines = hlo.splitlines()
        assert any("all-to-all" in l and "s8[" in l for l in lines)
        assert not any("all-gather" in l and "s8[" in l for l in lines)
        # the norm scale moved by an SGD update, not by wire-grid snapping:
        # vs the replicated step it may differ only through the gradient
        # wire (grads grid <7,1> -> update diff <= lr * 0.5), never by a
        # <2,6> params-grid snap of its ~1.0 value
        qcfg_ref = qtrain.QuantConfig(enabled=True,
                                      hyper_weights=DPSHyper(il_init=2,
                                                             fl_init=14))
        s_ref, _ = jax.jit(qtrain.make_train_step(loss_fn, opt, qcfg_ref))(
            qtrain.TrainState.create(params, opt.init(params), qcfg_ref,
                                     jax.random.key(3)), batch)
        diff = jnp.abs(s1.params["out_norm_scale"]
                       - s_ref.params["out_norm_scale"])
        assert float(diff.max()) <= 0.01 * 0.5 + 1e-6, diff
        print("OK")
    """)


def test_zero_partitioner_non_divisible_roundtrip():
    """(d): 37 elements over 8 ranks (pad 3) survive flatten -> slice-per-
    rank -> shard-local SGD step -> all-gather -> unflatten, and the pad
    region stays zero."""
    run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.dist.sharding import ZeroPartitioner
        from repro.optim import SGDConfig, make_optimizer
        from repro.dist.sharding import make_mesh

        tree = {"a": jnp.arange(15.0).reshape(3, 5) / 16,
                "b": jnp.arange(7.0)[::-1] / 8,
                "c": jnp.arange(15.0).reshape(5, 3).astype(jnp.bfloat16)}
        part = ZeroPartitioner.create(tree, 8)
        assert part.size == 37 and part.shard_size == 5
        assert part.padded_size == 40

        flat = part.flatten(tree)
        assert flat.shape == (40,) and flat.dtype == jnp.float32
        assert float(jnp.abs(flat[37:]).max()) == 0.0, "pad must be zero"
        back = part.unflatten(flat)
        for k in tree:
            assert back[k].dtype == tree[k].dtype
            np.testing.assert_array_equal(np.asarray(tree[k], np.float32),
                                          np.asarray(back[k], np.float32))

        # scatter / shard-local step / gather on a real 8-rank mesh
        mesh = make_mesh((8,), ("data",))
        opt = make_optimizer(SGDConfig(lr=0.5, momentum=0.0,
                                       weight_decay=0.0, schedule="const"))
        g = part.flatten(jax.tree.map(jnp.ones_like, tree))

        def body(gf, pf, mu):
            r = jax.lax.axis_index("data")
            upd, st = opt.update_shard(part.shard(gf, r), {"mu": mu},
                                       part.shard(pf, r),
                                       jnp.zeros((), jnp.int32),
                                       axis_name="data")
            return jax.lax.all_gather(part.shard(pf, r) + upd, "data",
                                      axis=0, tiled=True), st["mu"]

        fn = jax.jit(jax.shard_map(body, mesh=mesh,
                                   in_specs=(P(), P(), P("data")),
                                   out_specs=(P(), P("data")),
                                   check_vma=False))
        new_flat, mu = fn(g, flat, jnp.zeros((40,)))
        assert mu.shape == (40,)
        new_tree = part.unflatten(new_flat)
        for k in tree:
            np.testing.assert_allclose(
                np.asarray(new_tree[k], np.float32),
                np.asarray(tree[k], np.float32) - 0.5, atol=1e-6)
        # gradient 1.0 in the pad region would move it; the pad gradient is
        # zero by construction so the pad stays zero
        assert float(jnp.abs(new_flat[37:]).max()) == 0.0
        print("OK")
    """)
