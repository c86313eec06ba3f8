"""Fault-injection suite for ``repro.resilience`` (ISSUE-10).

Every guard is proven by firing its fault and watching the recovery:

  (a) transparency — guards armed with no fault are BIT-EXACT with the
      guard-free step (loss, params, DPS trajectory) at ``bits=None``,
      nearest@8 and stochastic@8;
  (b) NaN gradients — detected pre-encode (the int8 codec clips NaN
      silently), update skipped bit-exactly, wire degrades to fp32,
      int8 re-arms after the cooldown;
  (c) overflow storm — per-domain overflow EWMA trips, wire degrades,
      training recovers into the un-faulted loss envelope;
  (d) wire payload bit-flip — the gradient-norm spike guard catches the
      decoded offset, the poisoned step is skipped;
  (e) torn/corrupt checkpoints — SHA-256 digests make ``latest_step``
      walk back to the newest good step and ``restore`` fail loudly;
  (f) pre-emption — a REAL ``SIGTERM`` mid-run checkpoints and exits 0,
      and ``--resume`` continues (even after the newest checkpoint is
      corrupted on top);
  (g) loss-spike rollback — the host-side snapshot ring restores a
      healthy state after divergence the in-step guards can't see;
  (h) serve backpressure — page-pool exhaustion holds requests in the
      queue instead of crashing; every request completes;
  (i) the flow verifier's ``PF-GUARD-TAINT`` rule — degradation signals
      must descend from wire-leg stats (positive + negative oracle).

Multi-device pieces run in subprocesses under
``xla_force_host_platform_device_count=8`` (the repo-wide idiom).
"""

import dataclasses
import os
import signal
import subprocess
import sys
import textwrap

import numpy as np
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
# Health word plumbing (host-side, no devices).
# ---------------------------------------------------------------------------

def test_health_flags_decode():
    from repro.resilience import (HEALTH_DEGRADED, HEALTH_GRADS_NONFINITE,
                                  HEALTH_SKIPPED, health_flags)
    word = HEALTH_GRADS_NONFINITE | HEALTH_DEGRADED | HEALTH_SKIPPED
    assert health_flags(word) == ("grads-nonfinite", "degraded", "skipped")
    assert health_flags(0) == ()


# ---------------------------------------------------------------------------
# (a) transparency: armed guards with no fault are bit-exact.
# ---------------------------------------------------------------------------

def test_guards_transparent_across_rounding_modes():
    run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.core import fixed_point as fxp
        from repro.core import qtrain
        from repro.models import lenet
        from repro.optim import SGDConfig, make_optimizer
        from repro.resilience import GuardConfig
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        opt = make_optimizer(SGDConfig())
        params = lenet.init(jax.random.key(0))
        batch = {"images": jax.random.normal(jax.random.key(2),
                                             (64, 28, 28, 1)),
                 "labels": jax.random.randint(jax.random.key(3), (64,),
                                              0, 10)}

        variants = [
            ("bits=None", dict(enabled=True), None),
            ("nearest@8", dict(enabled=True, grad_allreduce_bits=8,
                               rounding=fxp.ROUND_NEAREST), mesh),
            ("stochastic@8", dict(enabled=True, grad_allreduce_bits=8), mesh),
        ]
        for name, kw, m in variants:
            q0 = qtrain.QuantConfig(**kw)
            qg = qtrain.QuantConfig(**kw, guards=GuardConfig())
            s0 = qtrain.TrainState.create(params, opt.init(params), q0,
                                          jax.random.key(1))
            sg = qtrain.TrainState.create(params, opt.init(params), qg,
                                          jax.random.key(1))
            f0 = jax.jit(qtrain.make_train_step(lenet.loss_fn, opt, q0,
                                                mesh=m))
            fg = jax.jit(qtrain.make_train_step(lenet.loss_fn, opt, qg,
                                                mesh=m))
            for i in range(3):
                s0, m0 = f0(s0, batch)
                sg, mg = fg(sg, batch)
                assert float(m0["loss"]) == float(mg["loss"]), (name, i)
            for a, b in zip(jax.tree.leaves(s0.params),
                            jax.tree.leaves(sg.params)):
                assert jnp.array_equal(a, b), name
            for a, b in zip(jax.tree.leaves(s0.dps),
                            jax.tree.leaves(sg.dps)):
                assert jnp.array_equal(a, b), name
            assert int(sg.guard.health) == 0, name
            assert int(sg.guard.skipped) == 0, name
            assert int(sg.guard.trips) == 0, name
            print(name, "transparent")
    """)


# ---------------------------------------------------------------------------
# (b) NaN gradients: detect -> skip -> degrade -> cooldown -> re-arm.
# ---------------------------------------------------------------------------

def test_nan_fault_skip_degrade_rearm():
    run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.core import qtrain
        from repro.models import lenet
        from repro.optim import SGDConfig, make_optimizer
        from repro.resilience import (FaultPlan, GuardConfig,
                                      HEALTH_GRADS_NONFINITE, HEALTH_SKIPPED)
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        opt = make_optimizer(SGDConfig())
        params = lenet.init(jax.random.key(0))
        batch = {"images": jax.random.normal(jax.random.key(2),
                                             (64, 28, 28, 1)),
                 "labels": jax.random.randint(jax.random.key(3), (64,),
                                              0, 10)}
        qcfg = qtrain.QuantConfig(enabled=True, grad_allreduce_bits=8,
                                  guards=GuardConfig(cooldown=3))
        s = qtrain.TrainState.create(params, opt.init(params), qcfg,
                                     jax.random.key(1))
        step = jax.jit(qtrain.make_train_step(
            lenet.loss_fn, opt, qcfg, mesh=mesh,
            faults=FaultPlan(nan_grads_at=2)))
        hist = []
        for i in range(8):
            prev = s.params
            s, m = step(s, batch)
            hist.append((int(m["health"]), int(m["degraded"]),
                         int(m["skipped"])))
            if i == 2:
                # the poisoned update is skipped BIT-EXACTLY
                for a, b in zip(jax.tree.leaves(prev),
                                jax.tree.leaves(s.params)):
                    assert jnp.array_equal(a, b)
        h2 = hist[2][0]
        assert h2 & HEALTH_GRADS_NONFINITE and h2 & HEALTH_SKIPPED, hist
        assert hist[2][2] == 1 and hist[7][2] == 1, hist   # exactly one skip
        assert hist[3][1] == 1, hist       # degraded right after the trip
        assert hist[7][1] == 0, hist       # int8 re-armed after cooldown
        assert int(s.guard.trips) == 1
        assert all(bool(jnp.isfinite(l).all())
                   for l in jax.tree.leaves(s.params))
        print("nan recovery OK", hist)
    """)


# ---------------------------------------------------------------------------
# (c) overflow storm: EWMA trip -> degrade -> recover into the envelope.
# ---------------------------------------------------------------------------

def test_overflow_storm_degrade_and_recover():
    run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.core import qtrain
        from repro.models import lenet
        from repro.optim import SGDConfig, make_optimizer
        from repro.resilience import (FaultPlan, GuardConfig,
                                      HEALTH_OVERFLOW_STORM)
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        opt = make_optimizer(SGDConfig())
        params = lenet.init(jax.random.key(0))
        batch = {"images": jax.random.normal(jax.random.key(2),
                                             (64, 28, 28, 1)),
                 "labels": jax.random.randint(jax.random.key(3), (64,),
                                              0, 10)}

        def run(faults, steps):
            qcfg = qtrain.QuantConfig(enabled=True, grad_allreduce_bits=8,
                                      guards=GuardConfig(cooldown=3))
            s = qtrain.TrainState.create(params, opt.init(params), qcfg,
                                         jax.random.key(1))
            fn = jax.jit(qtrain.make_train_step(lenet.loss_fn, opt, qcfg,
                                                mesh=mesh, faults=faults))
            hist = []
            for i in range(steps):
                s, m = fn(s, batch)
                hist.append((int(m["health"]), int(m["degraded"]),
                             float(m["loss"])))
            return s, hist

        s0, clean = run(None, 12)
        sf, hist = run(FaultPlan(overflow_storm_at=2, storm_steps=2,
                                 storm_scale=float(2 ** 12)), 12)
        # detection within the storm window
        assert any(h[0] & HEALTH_OVERFLOW_STORM for h in hist[2:5]), hist
        # degradation engaged, then re-armed by the end
        assert any(h[1] for h in hist[2:8]), hist
        assert hist[-1][1] == 0, hist
        assert int(sf.guard.trips) >= 1
        # recovery: params finite, final loss inside the un-faulted
        # envelope (generous: the storm steps still moved the params)
        assert all(bool(jnp.isfinite(l).all())
                   for l in jax.tree.leaves(sf.params))
        lf, l0 = hist[-1][2], clean[-1][2]
        import math
        assert math.isfinite(lf), hist
        assert lf < 2.0 * l0 + 1.0, (lf, l0)
        print("storm recovery OK", hist)
    """)


# ---------------------------------------------------------------------------
# (d) wire payload bit-flip: spike guard catches transport corruption.
# ---------------------------------------------------------------------------

def test_wire_bitflip_spike_detected_and_skipped():
    run_with_devices("""
        import jax, jax.numpy as jnp
        from repro.core import qtrain
        from repro.models import lenet
        from repro.optim import SGDConfig, make_optimizer
        from repro.resilience import (FaultPlan, GuardConfig,
                                      HEALTH_GRAD_SPIKE, HEALTH_SKIPPED)
        from repro.dist.sharding import make_mesh

        mesh = make_mesh((8,), ("data",))
        opt = make_optimizer(SGDConfig())
        params = lenet.init(jax.random.key(0))
        batch = {"images": jax.random.normal(jax.random.key(2),
                                             (64, 28, 28, 1)),
                 "labels": jax.random.randint(jax.random.key(3), (64,),
                                              0, 10)}
        qcfg = qtrain.QuantConfig(enabled=True, grad_allreduce_bits=8,
                                  guards=GuardConfig(cooldown=2))
        s = qtrain.TrainState.create(params, opt.init(params), qcfg,
                                     jax.random.key(1))
        step = jax.jit(qtrain.make_train_step(
            lenet.loss_fn, opt, qcfg, mesh=mesh,
            faults=FaultPlan(wire_flip_at=3)))
        hist = []
        for i in range(8):
            prev = s.params
            s, m = step(s, batch)
            hist.append((int(m["health"]), int(m["degraded"])))
            if i == 3:
                for a, b in zip(jax.tree.leaves(prev),
                                jax.tree.leaves(s.params)):
                    assert jnp.array_equal(a, b)   # poisoned sync skipped
        h3 = hist[3][0]
        assert h3 & HEALTH_GRAD_SPIKE and h3 & HEALTH_SKIPPED, hist
        assert hist[4][1] == 1, hist   # degraded after the flip
        assert hist[7][1] == 0, hist   # re-armed
        assert all(bool(jnp.isfinite(l).all())
                   for l in jax.tree.leaves(s.params))
        print("bit-flip detection OK", hist)
    """)


# ---------------------------------------------------------------------------
# (e) checkpoint integrity: digests, walk-back, loud restore failure.
# ---------------------------------------------------------------------------

def _small_tree():
    import jax
    import jax.numpy as jnp
    return {"w": jnp.arange(12, dtype=jnp.float32).reshape(3, 4),
            "n": {"b": jnp.ones((5,), jnp.float32)},
            "k": jax.random.key(7),
            "s": jnp.int32(3)}


def test_ckpt_digests_walk_back_past_corruption(tmp_path):
    import jax
    from repro.checkpoint import latest_step, restore, save, verify_step
    from repro.resilience import corrupt_checkpoint

    t = _small_tree()
    for s in (1, 2, 3):
        save(str(tmp_path), s, t)
    assert latest_step(str(tmp_path)) == 3
    assert verify_step(str(tmp_path), 3)

    # torn npz (truncated write that survived the rename)
    corrupt_checkpoint(str(tmp_path), 3, mode="truncate")
    assert not verify_step(str(tmp_path), 3)
    assert latest_step(str(tmp_path)) == 2          # walked back
    # silent bit-rot: npz still opens, digest must catch it
    corrupt_checkpoint(str(tmp_path), 2, mode="bitflip")
    assert latest_step(str(tmp_path)) == 1
    # unverified scan still sees the newest dir (the old hole, explicit)
    assert latest_step(str(tmp_path), verify=False) == 3

    # restore of the good step round-trips
    template = jax.eval_shape(lambda: _small_tree())
    restored, _ = restore(str(tmp_path), 1, template)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(t)):
        if jax.dtypes.issubdtype(a.dtype, jax.dtypes.prng_key):
            a, b = jax.random.key_data(a), jax.random.key_data(b)
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # restore of corrupted steps fails LOUDLY, never silently
    with pytest.raises(Exception):
        restore(str(tmp_path), 3, template)
    with pytest.raises(ValueError, match="SHA-256"):
        restore(str(tmp_path), 2, template)


# ---------------------------------------------------------------------------
# (f) pre-emption: SIGTERM checkpoints + exits 0; resume survives a
#     corrupted newest checkpoint on top.
# ---------------------------------------------------------------------------

def _train_cli(extra, tmp_path, n_dev=2):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    args = [sys.executable, "-m", "repro.launch.train",
            "--arch", "llama3_2_3b", "--smoke", "--steps", "8",
            "--batch", "2", "--seq", "16", "--optimizer", "sgd",
            "--grad-allreduce-bits", "8", "--guards",
            "--ckpt-dir", str(tmp_path), "--ckpt-every", "2",
            "--log-every", "2"] + extra
    return subprocess.run(args, capture_output=True, text=True, env=env,
                          timeout=600)


def test_sigterm_preemption_checkpoints_and_resumes(tmp_path):
    from repro.checkpoint import latest_step
    from repro.resilience import corrupt_checkpoint

    out = _train_cli(["--sigterm-at", "5"], tmp_path)
    assert out.returncode == 0, f"{out.stdout}\n{out.stderr}"
    assert "PREEMPTED" in out.stdout, out.stdout
    pre = latest_step(str(tmp_path))
    assert pre is not None and pre >= 5, out.stdout

    # disk rot on top of the pre-emption: resume must fall back to the
    # newest GOOD checkpoint and still finish
    corrupt_checkpoint(str(tmp_path), pre, mode="truncate")
    good = latest_step(str(tmp_path))
    assert good is not None and good < pre

    out2 = _train_cli(["--resume"], tmp_path)
    assert out2.returncode == 0, f"{out2.stdout}\n{out2.stderr}"
    assert f"resumed from step {good}" in out2.stdout, out2.stdout
    assert "final_loss" in out2.stdout


# ---------------------------------------------------------------------------
# (g) loss-spike rollback ring (host side).
# ---------------------------------------------------------------------------

def test_rollback_ring_restores_healthy_state(capsys):
    """NaN gradients at step 5 with NO in-step guards: params go NaN,
    the drained window turns nonfinite, the ring rolls back to the
    step-5 snapshot and replays.  The fault is step-keyed, so every
    deterministic replay re-fires it — which is exactly what proves the
    restore: each replayed window's step-5 FORWARD loss is finite again
    (computed on the restored params, before the NaN grads re-poison
    them).  The rollback cap bounds the livelock and the driver still
    completes instead of crashing."""
    from repro.launch import train as train_mod
    hist = train_mod.main([
        "--arch", "llama3_2_3b", "--smoke", "--steps", "10",
        "--batch", "2", "--seq", "16", "--optimizer", "sgd",
        "--inject-nan-at", "5", "--rollback-ring", "2",
        "--log-every", "2"])
    out = capsys.readouterr().out
    n_rb = out.count("ROLLBACK")
    assert 1 <= n_rb <= 8, out
    assert "resuming from step 5 with wire degraded" in out, out
    # every rollback restored HEALTHY params: each replayed window
    # re-runs step 5's forward on the restored snapshot and drains a
    # finite loss before the re-fired fault poisons step 6 again
    losses = [h["loss"] for h in hist]
    first_bad = next(i for i, l in enumerate(losses) if not np.isfinite(l))
    finite_after = sum(1 for l in losses[first_bad:] if np.isfinite(l))
    assert finite_after >= n_rb, (n_rb, losses)
    # the run pushed through after the cap instead of looping forever
    assert len(hist) > 0 and not np.isfinite(losses[-1])


# ---------------------------------------------------------------------------
# (h) serve backpressure: pool exhaustion holds, drains, loses nothing.
# ---------------------------------------------------------------------------

def test_scheduler_requeue_preserves_fcfs():
    from repro.serve import Request, Scheduler
    reqs = [Request(rid=i, prompt=np.ones(4, np.int32), max_new=2,
                    arrival=0) for i in range(3)]
    s = Scheduler(reqs)
    head = s.pop_admissible(0, lambda r: True)
    assert head.rid == 0
    s.requeue(head)
    assert len(s) == 3
    assert s.pop_admissible(0, lambda r: True).rid == 0   # still the head


def test_serve_backpressure_exhaustion_then_drain():
    """More lifetime-page demand than the pool holds: requests are held
    in the queue under backpressure and every one of them completes —
    none dropped, no crash."""
    import jax
    from repro.configs.base import get_config, smoke
    from repro.models import registry
    from repro.models.common import init_params
    from repro.serve import Engine, EngineConfig, PagedLayout, Request

    cfg = smoke(get_config("llama3_2_3b"))
    params = init_params(jax.random.key(0), registry(cfg.family).model_defs(cfg))
    # 12 pages; each request needs ceil((8 prompt + 8 new)/4) = 4 pages
    # -> at most 3 of the 4 batch slots can ever be live; the rest queue
    lay = PagedLayout(page_size=4, n_pages=12, batch_slots=4,
                      max_pages_per_seq=8, max_prompt=16)
    eng = Engine(cfg, params, EngineConfig(layout=lay, kv_bits=None))
    reqs = [Request(rid=i,
                    prompt=np.full(8, 3 + i, np.int32), max_new=8,
                    arrival=0) for i in range(6)]
    rep = eng.run(reqs)
    assert all(len(rep.tokens[r.rid]) == r.max_new for r in reqs)
    assert rep.metrics["backpressure_steps"] > 0


def test_serve_alloc_failure_requeues_instead_of_crashing(monkeypatch):
    """Force the defensive path: the admission pre-check lies (can()
    always True) so ``alloc.alloc`` raises mid-admit — the engine must
    requeue the request and finish the trace regardless."""
    import jax
    from repro.configs.base import get_config, smoke
    from repro.models import registry
    from repro.models.common import init_params
    from repro.serve import (Engine, EngineConfig, PageAllocator,
                             PagedLayout, Request)

    # keep the real alloc (it raises on exhaustion); lying in the
    # pre-check makes the mid-admit exhaustion path actually execute
    monkeypatch.setattr(PageAllocator, "can", lambda self, n: True)

    cfg = smoke(get_config("llama3_2_3b"))
    params = init_params(jax.random.key(0), registry(cfg.family).model_defs(cfg))
    lay = PagedLayout(page_size=4, n_pages=12, batch_slots=4,
                      max_pages_per_seq=8, max_prompt=16)
    eng = Engine(cfg, params, EngineConfig(layout=lay, kv_bits=None))
    reqs = [Request(rid=i, prompt=np.full(8, 3 + i, np.int32), max_new=8,
                    arrival=0) for i in range(5)]
    rep = eng.run(reqs)
    assert all(len(rep.tokens[r.rid]) == r.max_new for r in reqs)
    assert rep.metrics["backpressure_steps"] > 0


# ---------------------------------------------------------------------------
# (i) PF-GUARD-TAINT: degradation signals must descend from wire stats.
# ---------------------------------------------------------------------------

def _taint_jaxpr(make_signal):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.fixed_point import FixedPointFormat
    from repro.dist import collectives
    from repro.dist.sharding import make_mesh

    fmt = FixedPointFormat.create(3, 5)
    tree = {"leaf0": jnp.ones((64,), jnp.float32)}
    mesh = make_mesh((1,), ("data",))

    def body(tr, k):
        mean, stats = collectives.dps_allreduce_mean_tree(tr, fmt, "data", k)
        return mean, make_signal(stats)

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=({"leaf0": P()}, P()),
                       out_specs=({"leaf0": P()}, P()),
                       check_vma=False)
    return jax.make_jaxpr(fn)(tree, jax.random.key(0))


def test_flow_guard_taint_positive_and_negative():
    import jax.numpy as jnp
    from repro.analysis import flow
    from repro.core import tagging

    # a signal genuinely derived from the wire-leg stats: clean
    def good(stats):
        rate = jnp.sum(stats.overflow) / jnp.maximum(jnp.sum(stats.count), 1.0)
        return tagging.tag(rate, "guard_sink", domain="wire_grads")

    rep = flow.analyze_jaxpr(_taint_jaxpr(good), name="guard-taint-good")
    assert "PF-GUARD-TAINT" in rep.checked
    assert not [v for v in rep.violations if v.rule == "PF-GUARD-TAINT"], \
        rep.summary()

    # a constant masquerading as a health signal in a wire step: flagged
    def bad(stats):
        return tagging.tag(jnp.float32(0.0), "guard_sink",
                           domain="wire_grads")

    rep = flow.analyze_jaxpr(_taint_jaxpr(bad), name="guard-taint-bad")
    bad_v = [v for v in rep.violations if v.rule == "PF-GUARD-TAINT"]
    assert bad_v, rep.summary()


def test_lint_guarded_cell_clean():
    """The full guarded train cell passes flow + HLO audit: the compiled
    fp32 fallback branches are declared bytes, not residual leakage."""
    run_with_devices("""
        from repro.analysis import lint
        reports = lint.lint_cell("lenet", "tree", guards=True)
        flow_rep = reports[0]
        assert "PF-GUARD-TAINT" in flow_rep.checked, flow_rep.checked
        for r in reports:
            assert not r.violations, r.summary()
        print("guarded lint cell clean")
    """)
