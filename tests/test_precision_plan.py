"""Precision-domain registry tests (ISSUE-4): PrecisionPlan / DpsBundle.

Property tests: ANY plan — random domain names, controller kinds, group
counts, hypers — must (a) build a DpsBundle that round-trips through
``jit`` and ``shard_map`` as a pytree with stable structure, (b) update
under partial stats streams (absent streams read as zero), and (c) leave
the training step bit-exact at ``bits=None``: domains nobody feeds or
reads cannot perturb the parameter trajectory.

Plus the checkpoint schema upgrade: a legacy checkpoint carrying only the
three-key compute DPS bundle restores into a five-domain registry with
the wire domains initialized fresh (``ckpt.restore(defaults=...)``).
"""

import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import qtrain
from repro.core.dps import (CONTROLLERS, DomainSpec, DpsBundle, DPSHyper,
                            PrecisionPlan, wire_hyper)
from repro.core.fixed_point import QuantStats
from repro.dist.sharding import make_mesh


def random_plan(rng: random.Random, max_domains: int = 5) -> PrecisionPlan:
    names = rng.sample(["weights", "acts", "grads", "wire_grads",
                        "wire_params", "kv_cache", "moe_router", "opt_state"],
                       rng.randint(1, max_domains))
    groups = {n: rng.choice([0, 0, 1, 3, 4]) for n in names}
    domains = []
    for n in names:
        kind = rng.choice(sorted(CONTROLLERS))
        hyper = DPSHyper(il_init=rng.randint(2, 10),
                         fl_init=rng.randint(1, 14),
                         total_bits=rng.choice([8, 12, 16]),
                         r_max=rng.choice([1e-4, 5e-3]),
                         e_max=rng.choice([1e-4, 5e-2]))
        # routed streams must be scalar or match the domain's group count
        # (PrecisionPlan.update enforces this; pinned below) — route only
        # to shape-compatible targets, plus absent streams
        targets = [m for m in names
                   if groups[m] == groups[n] or groups[m] == 0]
        domains.append((n, DomainSpec(
            controller=kind, hyper=hyper,
            stats=rng.choice(["", n, rng.choice(targets), "absent_stream"]),
            groups=groups[n])))
    return PrecisionPlan(tuple(domains))


def random_stats(rng: random.Random, shape=()) -> QuantStats:
    full = lambda v: jnp.full(shape, v, jnp.float32)
    n = rng.randint(100, 10_000)
    return QuantStats(count=full(n), nonzero=full(n * 0.9),
                      overflow=full(rng.randint(0, 50)),
                      abs_err_sum=full(rng.uniform(0, 10)),
                      rel_err_sum=full(rng.uniform(0, 100)),
                      abs_sum=full(rng.uniform(1, 100)),
                      max_abs=full(rng.uniform(0.1, 64.0)))


def test_random_plans_roundtrip_jit_and_shard_map_as_pytrees():
    rng = random.Random(0)
    mesh = make_mesh((jax.device_count(),), ("data",))
    for trial in range(12):
        plan = random_plan(rng)
        bundle = plan.init()
        assert isinstance(bundle, DpsBundle)
        assert bundle.names() == plan.names
        # formats honor the declared group count
        fmts = plan.formats(bundle)
        for name, spec in plan.domains:
            assert fmts[name].il.shape == spec.state_shape(), (trial, name)

        # streams for a random subset of domains (others read zero stats)
        streams = {n: random_stats(rng, s.state_shape())
                   for n, s in plan.domains if rng.random() < 0.7}
        aux = {"loss": jnp.float32(rng.uniform(0.1, 10.0))}

        # jit round-trip: structure stable, updatable, formats extractable
        upd = jax.jit(lambda b: plan.update(b, streams, aux))
        b1 = upd(bundle)
        assert jax.tree.structure(b1) == jax.tree.structure(bundle), trial
        b2 = upd(b1)
        assert jax.tree.structure(b2) == jax.tree.structure(bundle), trial

        # flatten/unflatten identity (checkpoint + donation path)
        leaves, treedef = jax.tree_util.tree_flatten(b2)
        b3 = jax.tree_util.tree_unflatten(treedef, leaves)
        for a, b in zip(jax.tree.leaves(b2), jax.tree.leaves(b3)):
            assert jnp.array_equal(a, b)

        # shard_map round-trip: the bundle is replicated controller state
        body = jax.shard_map(lambda b: plan.update(b, streams, aux),
                             mesh=mesh, in_specs=P(), out_specs=P(),
                             check_vma=False)
        b4 = jax.jit(body)(bundle)
        assert jax.tree.structure(b4) == jax.tree.structure(bundle), trial
        for a, b in zip(jax.tree.leaves(b1), jax.tree.leaves(b4)):
            assert jnp.array_equal(a, b), (trial, "shard_map != jit")


def test_plan_validation_rejects_bad_declarations():
    with pytest.raises(ValueError, match="duplicate"):
        PrecisionPlan((("a", DomainSpec()), ("a", DomainSpec())))
    with pytest.raises(ValueError, match="unknown controller"):
        PrecisionPlan((("a", DomainSpec(controller="nope")),))
    with pytest.raises(ValueError, match="groups"):
        PrecisionPlan((("a", DomainSpec(groups=-1)),))
    plan = PrecisionPlan((("a", DomainSpec()),))
    with pytest.raises(KeyError):
        plan.spec("missing")
    # a routed stream whose [G] shape mismatches the consumer fails loudly
    # instead of silently reshaping the domain's controller state
    bad = PrecisionPlan((
        ("grads", DomainSpec(groups=4)),
        ("scalar_consumer", DomainSpec(stats="grads", groups=0)),
    ))
    rng = random.Random(3)
    with pytest.raises(ValueError, match="scalar or match"):
        bad.update(bad.init(), {"grads": random_stats(rng, (4,))},
                   {"loss": jnp.float32(1.0)})
    off_by_one = PrecisionPlan((
        ("grads", DomainSpec(groups=4)),
        ("grouped_consumer", DomainSpec(stats="grads", groups=3)),
    ))
    with pytest.raises(ValueError, match="scalar or match"):
        off_by_one.update(off_by_one.init(),
                          {"grads": random_stats(rng, (4,))},
                          {"loss": jnp.float32(1.0)})


def test_stats_routing_and_scalar_broadcast_to_groups():
    rng = random.Random(7)
    plan = PrecisionPlan((
        ("grads", DomainSpec("paper", DPSHyper())),
        # routed: consumes the grads stream despite its own name
        ("shadow", DomainSpec("paper", DPSHyper(), stats="grads")),
        # per-group domain fed by the (scalar) grads stream -> broadcast
        ("grouped", DomainSpec("paper", DPSHyper(), stats="grads", groups=3)),
    ))
    bundle = plan.init()
    st = random_stats(rng)
    out = plan.update(bundle, {"grads": st}, {"loss": jnp.float32(1.0)})
    # same controller, same hyper, same stats -> identical moves
    assert jnp.array_equal(out["grads"].il, out["shadow"].il)
    assert out["grouped"].il.shape == (3,)
    np.testing.assert_array_equal(np.asarray(out["grouped"].il),
                                  np.full((3,), int(out["grads"].il)))


def test_group_stream_routes_group_wise_into_grouped_domain():
    """A [G] stats stream drives each group's controller row independently
    (the per-layer wire regime: group g's wire stats move only group g's
    ⟨IL, FL⟩), and a shape-mismatched stream still raises."""
    plan = PrecisionPlan((
        ("wire_grads", DomainSpec("flexpoint",
                                  DPSHyper(total_bits=8, il_min=1,
                                           il_init=4), groups=3)),
    ))
    bundle = plan.init()
    zero = jnp.zeros((3,), jnp.float32)
    # only group 1 observes a large max |g|
    st = QuantStats(count=jnp.full((3,), 100.0), nonzero=jnp.full((3,), 90.0),
                    overflow=zero, abs_err_sum=zero, rel_err_sum=zero,
                    abs_sum=zero,
                    max_abs=jnp.asarray([0.01, 40.0, 0.01], jnp.float32))
    out = plan.update(bundle, {"wire_grads": st}, None)
    il = np.asarray(out["wire_grads"].il)
    assert il[1] > il[0] and il[1] > il[2], il  # radix follows ITS group
    assert il[0] == il[2], il
    with pytest.raises(ValueError, match="scalar or match"):
        bad = jax.tree.map(lambda x: jnp.broadcast_to(x[:2], (2,)), st)
        plan.update(bundle, {"wire_grads": bad}, None)


def test_with_per_layer_wire_sets_groups_from_leaf_count():
    params = {"a": jnp.zeros((3, 4)), "b": {"w": jnp.zeros((5,)),
                                            "s": jnp.zeros(())}}
    base = qtrain.QuantConfig(enabled=True)
    assert base.with_per_layer_wire(params) is base    # no wire -> no-op
    qcfg = qtrain.QuantConfig(enabled=True, grad_allreduce_bits=8
                              ).with_per_layer_wire(params)
    assert qcfg.wire_grads_groups == 3
    assert qcfg.plan().spec("wire_grads").groups == 3
    bundle = qtrain.init_dps_bundle(qcfg)
    assert bundle["wire_grads"].il.shape == (3,)
    # the [G] formats surface in bundle_formats for the collectives' table
    fmts = qtrain.bundle_formats(qcfg, bundle)
    assert fmts["wire_grads"].il.shape == (3,)


def test_per_layer_wire_with_zero_opt_raises():
    from repro.models import lenet
    from repro.optim import SGDConfig, make_optimizer
    params = lenet.init(jax.random.key(0))
    qcfg = qtrain.QuantConfig(enabled=True, grad_allreduce_bits=8,
                              zero_opt_shards=1
                              ).with_per_layer_wire(params)
    mesh = make_mesh((1,), ("data",))
    opt = make_optimizer(SGDConfig())
    # single-device mesh: neither path engages, so the build succeeds ...
    qtrain.make_train_step(lenet.loss_fn, opt, qcfg, mesh=mesh)
    # ... but an engaging ZeRO mesh must reject per-layer wire groups
    # (the flat partitioner layout erases leaf boundaries).  Exercised
    # through the validation directly: fake an engaged config check via
    # a 1-axis mesh of the real device count when >1 devices exist.
    if jax.device_count() > 1:
        n = jax.device_count()
        mesh_n = make_mesh((n,), ("data",))
        qcfg_n = qtrain.QuantConfig(enabled=True, grad_allreduce_bits=8,
                                    zero_opt_shards=n
                                    ).with_per_layer_wire(params)
        with pytest.raises(ValueError, match="per-layer wire"):
            qtrain.make_train_step(lenet.loss_fn, opt, qcfg_n, mesh=mesh_n)


def test_bits_none_step_bitexact_under_extra_domains():
    """Domains nobody feeds or reads cannot perturb training: a plan with
    wire + custom domains produces the identical parameter trajectory to
    the standard three-domain plan at ``bits=None``."""
    from repro.models import lenet
    from repro.optim import SGDConfig, make_optimizer

    opt = make_optimizer(SGDConfig())
    params = lenet.init(jax.random.key(0))
    batch = {"images": jax.random.normal(jax.random.key(2), (16, 28, 28, 1)),
             "labels": jax.random.randint(jax.random.key(3), (16,), 0, 10)}

    qcfg_std = qtrain.QuantConfig(enabled=True)
    base = qcfg_std.plan()
    qcfg_ext = qtrain.QuantConfig(enabled=True, precision_plan=PrecisionPlan(
        base.domains + (
            ("wire_grads", DomainSpec("flexpoint", wire_hyper(8, 6, -2.0))),
            ("wire_params", DomainSpec("flexpoint", wire_hyper(8, 2, 1.0))),
            ("kv_cache", DomainSpec("static", DPSHyper(il_init=8,
                                                       fl_init=8))),
        )))

    def run(qcfg, steps=3):
        state = qtrain.TrainState.create(params, opt.init(params), qcfg,
                                         jax.random.key(1))
        step = jax.jit(qtrain.make_train_step(lenet.loss_fn, opt, qcfg))
        losses = []
        for _ in range(steps):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
        return state, losses

    s_std, l_std = run(qcfg_std)
    s_ext, l_ext = run(qcfg_ext)
    assert l_std == l_ext
    for a, b in zip(jax.tree.leaves(s_std.params),
                    jax.tree.leaves(s_ext.params)):
        assert jnp.array_equal(a, b), "extra domains perturbed the params"
    # the compute-domain trajectories match too
    for k in ("weights", "acts", "grads"):
        for a, b in zip(jax.tree.leaves(s_std.dps[k]),
                        jax.tree.leaves(s_ext.dps[k])):
            assert jnp.array_equal(a, b)


def test_ckpt_legacy_three_key_bundle_upgrades_to_registry(tmp_path):
    """Round-trip: a checkpoint written with the legacy dict-of-three DPS
    bundle restores into a wire-domain registry — compute domains carry
    their checkpointed trajectories, wire domains initialize fresh."""
    from repro.checkpoint import restore, save
    from repro.models import lenet
    from repro.optim import SGDConfig, make_optimizer

    opt = make_optimizer(SGDConfig())
    params = lenet.init(jax.random.key(0))
    qcfg_new = qtrain.QuantConfig(enabled=True, grad_allreduce_bits=8,
                                  zero_opt_shards=8)

    # a legacy state: plain {attr: controller state} dict, with visibly
    # non-initial trajectories so the restore is distinguishable
    legacy_dps = {
        "weights": qcfg_new.plan().controller("weights").init(),
        "acts": qcfg_new.plan().controller("acts").init(),
        "grads": qcfg_new.plan().controller("grads").init(),
    }
    legacy_dps["grads"] = jax.tree.map(lambda x: x + 3, legacy_dps["grads"])
    legacy_state = qtrain.TrainState(
        step=jnp.asarray(17, jnp.int32), params=params,
        opt_state=opt.init(params), dps=legacy_dps,
        rng=jax.random.key(5), last_loss=jnp.float32(1.25))
    save(str(tmp_path), 17, legacy_state, meta={"cursor": 17})

    # restore into the registry template (five domains)
    template = jax.eval_shape(
        lambda: qtrain.TrainState.create(params, opt.init(params), qcfg_new,
                                         jax.random.key(1)))
    with pytest.raises(KeyError):
        restore(str(tmp_path), 17, template)   # without defaults: loud
    restored, meta = restore(str(tmp_path), 17, template,
                             defaults=qtrain.dps_restore_defaults(qcfg_new))
    assert meta["cursor"] == 17
    assert restored.dps.names() == ("weights", "acts", "grads",
                                    "wire_grads", "wire_params")
    # compute domains: checkpointed values (grads trajectory +3)
    for a, b in zip(jax.tree.leaves(restored.dps["grads"]),
                    jax.tree.leaves(legacy_dps["grads"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # wire domains: fresh init
    fresh = qtrain.init_dps_bundle(qcfg_new)
    for dom in ("wire_grads", "wire_params"):
        for a, b in zip(jax.tree.leaves(restored.dps[dom]),
                        jax.tree.leaves(fresh[dom])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # params restored exactly
    for a, b in zip(jax.tree.leaves(restored.params),
                    jax.tree.leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
