"""Training driver: quantized (DPS) training with fault tolerance.

Production behaviors implemented here:
  * auto-resume from the newest complete checkpoint (``--resume``) —
    ``latest_step`` digest-verifies and walks past torn/corrupt step dirs,
  * atomic async checkpointing every ``--ckpt-every`` steps,
  * elastic restart — the checkpoint is mesh-agnostic, restore re-shards
    onto whatever mesh this invocation builds (different device count OK),
  * graceful pre-emption: SIGTERM/SIGINT checkpoints on the way down and
    exits 0 (a scheduler eviction is not a failure),
  * numeric health guards (``--guards``: repro.resilience in-step monitor,
    skip gate, fp32 wire degradation) plus a host-side loss-spike rollback
    ring (``--rollback-ring K``): the last K healthy train states are kept
    in host memory and a median-filtered loss spike rolls back to the
    newest one and forces the wire into its fp32 fallback for a cooldown,
  * failure injection (``--fail-at N`` crash, ``--inject-*-at N`` numeric
    faults, ``--sigterm-at N`` pre-emption) to exercise every recovery
    path in CI,
  * straggler/step watchdog: a step exceeding ``--step-timeout`` seconds
    raises, the driver checkpoints on the way down.

Smoke scale (CPU):
  PYTHONPATH=src python -m repro.launch.train --arch llama3_2_3b --smoke \
      --steps 20 --batch 4 --seq 64

Published widths at reduced depth (one TPU v5e holds four llama3_2_3b
layers at batch 2, seq 2048):
  PYTHONPATH=src python -m repro.launch.train --arch llama3_2_3b \
      --layers 4 --steps 8 --batch 2 --seq 2048
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import AsyncCheckpointer, latest_step, restore
from repro.configs.base import get_config, smoke as smoke_cfg
from repro.core import qtrain
from repro.device import enable_compile_cache
from repro.data import TokenStream, TokenStreamConfig
from repro.dist.sharding import (DEFAULT_RULES, LogicalRules, axis_rules,
                                 make_mesh)
from repro.launch import specs as specs_lib
from repro.models import registry
from repro.models.common import init_params
from repro.optim import AdamWConfig, SGDConfig, make_optimizer


def _to_host(x):
    """Rollback-ring entry leaf: host numpy (PRNG keys via key_data)."""
    if (hasattr(x, "dtype")
            and jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key)):
        return np.asarray(jax.random.key_data(x))
    return np.asarray(x)


def _from_host(arr, like):
    """Inverse of :func:`_to_host` against a template leaf.  Plain arrays
    stay host-side/uncommitted — the jitted step's ``in_shardings`` place
    them, so a rolled-back state reshards exactly like a restore."""
    if jax.dtypes.issubdtype(like.dtype, jax.dtypes.prng_key):
        return jax.random.wrap_key_data(jnp.asarray(arr))
    return np.asarray(arr, like.dtype)


def build(cfg, qcfg, opt_cfg, mesh=None, faults=None):
    opt = make_optimizer(opt_cfg)
    step_fn = specs_lib.build_train_step(cfg, qcfg, opt, mesh=mesh,
                                         faults=faults)
    if mesh is not None:
        # classic data parallelism on the 1-D data mesh, with the fp32
        # all-reduce, the int8 wire or ZeRO-1 alike: params replicate
        # across the data axis (the wire's shard_map pins them to P());
        # binding "fsdp" would re-gather every leaf per step.  Under ZeRO
        # the *optimizer state* shards instead, via the flat P("data")
        # layout in train_state_shardings.
        rules = LogicalRules(rules=tuple(
            r for r in DEFAULT_RULES if r[0] != "fsdp"))
        state_sh = specs_lib.train_state_shardings(cfg, mesh, rules, opt, qcfg)
        jitted = jax.jit(step_fn, in_shardings=(state_sh, None),
                         out_shardings=(state_sh, None), donate_argnums=(0,))
    else:
        jitted = jax.jit(step_fn, donate_argnums=(0,))
    return opt, jitted


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="override the config's n_layers (depth cut only; "
                         "every width stays as published)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--optimizer", choices=("sgd", "adamw"), default="adamw")
    ap.add_argument("--controller", default="paper",
                    help="DPS controller (paper|courbariaux|na_mukhopadhyay|"
                         "static|flexpoint) or 'off'")
    ap.add_argument("--grad-allreduce-bits", type=int, default=None,
                    help="compress the gradient all-reduce to an int8 wire "
                         "of this many grid bits (2-8); builds a data-axis "
                         "mesh over all local devices and feeds the wire "
                         "QuantStats into the dedicated wire_grads DPS "
                         "domain")
    ap.add_argument("--wire-controller",
                    default=os.environ.get("REPRO_WIRE_CONTROLLER")
                    or "flexpoint",
                    help="DPS controller kind for the wire precision "
                         "domains (wire_grads/wire_params); 'flexpoint' "
                         "(default) drives the wire radix from max|x|, "
                         "immune to the hair-trigger r_max IL ratchet "
                         "(see dist/README.md)")
    ap.add_argument("--wire-groups", choices=("per-layer", "global"),
                    default=os.environ.get("REPRO_WIRE_GROUPS")
                    or "per-layer",
                    help="granularity of the wire_grads ⟨IL, FL⟩: "
                         "'per-layer' (default) runs one format per "
                         "gradient leaf through the group-aligned "
                         "collectives ([G, 2] kernel format table); "
                         "'global' keeps the single shared wire format. "
                         "Composes with --zero-opt: the flat optimizer "
                         "layout switches to the group-aligned "
                         "partitioner, so per-leaf formats survive the "
                         "flatten and both sharded legs run the grouped "
                         "codec.  Resume with the same choice — the "
                         "wire_grads (and under ZeRO wire_params) ckpt "
                         "state is [G]-shaped under per-layer")
    ap.add_argument("--wire-overlap", choices=("on", "off"),
                    default=os.environ.get("REPRO_WIRE_OVERLAP") or "off",
                    help="backward-overlapped bucketed wire: split the "
                         "gradient tree into buckets and run one "
                         "compressed collective pair per bucket in "
                         "backward ready order (repro.dist.overlap), "
                         "instead of one monolithic pair after the full "
                         "backward.  Needs --grad-allreduce-bits.  "
                         "Composes with --zero-opt: the group-aligned "
                         "layout runs one int8 reduce-scatter per bucket "
                         "in the same backward-ready order")
    ap.add_argument("--wire-auto-slack", action="store_true",
                    default=bool(os.environ.get("REPRO_WIRE_AUTO_SLACK")),
                    help="derive each wire domain's radix headroom from "
                         "its measured abs_sum/nonzero tail quantile "
                         "(dps.wire_hyper(auto_slack=True)) instead of "
                         "the hand-tuned per-tensor-class slack "
                         "constants")
    ap.add_argument("--zero-opt", action="store_true",
                    help="ZeRO-1: shard the optimizer state across the "
                         "data axis (flat padded layout, 1/n state bytes "
                         "per device); combined with --grad-allreduce-bits "
                         "both the gradient reduce-scatter and the param "
                         "all-gather ride the int8 wire")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--guards", action="store_true",
                    help="arm the repro.resilience health guards: in-step "
                         "NaN/overflow/spike detection, skip gate, and "
                         "graceful int8-wire -> fp32 degradation with "
                         "cooldown re-arm")
    ap.add_argument("--guard-cooldown", type=int, default=16,
                    help="clean steps before a degraded wire domain "
                         "re-arms its int8 codec")
    ap.add_argument("--rollback-ring", type=int, default=0,
                    help="keep the last K healthy train states in host "
                         "memory (snapshotted at log points) and roll "
                         "back to the newest one on a median-filtered "
                         "loss spike; 0 disables")
    ap.add_argument("--rollback-spike", type=float, default=10.0,
                    help="drained loss > this factor times the median of "
                         "the recent drained losses triggers a rollback")
    ap.add_argument("--fail-at", type=int, default=0,
                    help="inject a crash after N steps (restart test)")
    ap.add_argument("--sigterm-at", type=int, default=0,
                    help="send SIGTERM to this process after N steps "
                         "(pre-emption test: checkpoint + exit 0)")
    ap.add_argument("--inject-nan-at", type=int, default=-1,
                    help="fault injection: NaN gradients at this step")
    ap.add_argument("--inject-storm-at", type=int, default=-1,
                    help="fault injection: overflow-storm gradient scale "
                         "starting at this step")
    ap.add_argument("--inject-wire-flip-at", type=int, default=-1,
                    help="fault injection: XOR a bit into the int8 wire "
                         "payload at this step")
    ap.add_argument("--step-timeout", type=float, default=0.0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_cfg(cfg)
    if args.layers is not None:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    n_dev = jax.device_count()
    zero_shards = n_dev if (args.zero_opt and n_dev > 1) else None
    guards = None
    if args.guards:
        from repro.resilience import GuardConfig
        guards = GuardConfig(cooldown=args.guard_cooldown)
    faults = None
    if (args.inject_nan_at >= 0 or args.inject_storm_at >= 0
            or args.inject_wire_flip_at >= 0):
        from repro.resilience import FaultPlan
        faults = FaultPlan(nan_grads_at=args.inject_nan_at,
                           overflow_storm_at=args.inject_storm_at,
                           wire_flip_at=args.inject_wire_flip_at)
    qcfg = qtrain.QuantConfig(enabled=args.controller != "off",
                              controller=args.controller
                              if args.controller != "off" else "paper",
                              grad_allreduce_bits=args.grad_allreduce_bits,
                              zero_opt_shards=zero_shards,
                              wire_controller=args.wire_controller,
                              wire_overlap=args.wire_overlap == "on",
                              wire_auto_slack=args.wire_auto_slack,
                              guards=guards)
    if args.wire_groups == "per-layer":
        # one wire ⟨IL, FL⟩ per gradient leaf; the group count derives
        # from the abstract param tree so the plan (and with it the DPS
        # checkpoint layout) is fixed before any tensor exists.  Under
        # --zero-opt this selects the group-aligned flat layout too.
        qcfg = specs_lib.per_layer_wire_qcfg(cfg, qcfg)
    # warmup is a tenth of the run, at most 100 steps: a short run leaves
    # warmup and sees the whole cosine decay
    opt_cfg = (AdamWConfig(total_steps=args.steps,
                           warmup=min(100, args.steps // 10))
               if args.optimizer == "adamw" else SGDConfig())
    mesh = None
    if n_dev > 1:
        # a pure data-parallel mesh over every local device: the batch
        # splits over it, and the gradients meet in the fp32 all-reduce or,
        # with --grad-allreduce-bits, the int8 wire.  On one device no mesh
        # is built and the wire and ZeRO-1 paths degrade to the plain step.
        mesh = make_mesh((n_dev,), ("data",))
    opt, jitted = build(cfg, qcfg, opt_cfg, mesh=mesh, faults=faults)
    if mesh is not None and args.grad_allreduce_bits is not None \
            and not jitted.wire_sync_active:
        raise SystemExit("--grad-allreduce-bits: the int8 gradient wire did "
                         "not engage on this mesh")
    print(f"step: {n_dev} device(s), wire_sync_active="
          f"{jitted.wire_sync_active}, zero_opt_active="
          f"{jitted.zero_opt_active}", flush=True)

    mod = registry(cfg.family)
    data = TokenStream(TokenStreamConfig(vocab=cfg.vocab, seq_len=args.seq,
                                         global_batch=args.batch,
                                         seed=args.seed))

    start = 0
    ckpt = None
    if args.ckpt_dir:
        ckpt = AsyncCheckpointer(args.ckpt_dir)
    if args.resume and args.ckpt_dir and latest_step(args.ckpt_dir) is not None:
        start = latest_step(args.ckpt_dir)
        template = specs_lib.abstract_train_state(cfg, opt, qcfg, mesh=mesh)
        # legacy checkpoints carry only the three-key compute DPS bundle;
        # domains the plan adds (e.g. wire_grads/wire_params) and the
        # guard subtree init fresh when the checkpoint predates them.
        defaults = qtrain.dps_restore_defaults(qcfg)
        defaults.update(qtrain.guard_restore_defaults(qcfg))
        state, meta = restore(args.ckpt_dir, start, template,
                              defaults=defaults)
        print(f"resumed from step {start} (data cursor {meta.get('cursor')})")
    else:
        params = init_params(jax.random.key(args.seed), mod.model_defs(cfg))
        if qtrain.zero_opt_engaged(qcfg, mesh):
            opt_state = qtrain.zero_opt_state(opt, params, zero_shards,
                                              qcfg=qcfg)
        else:
            opt_state = opt.init(params)
        state = qtrain.TrainState.create(params, opt_state, qcfg,
                                         jax.random.key(args.seed + 1))

    extras = {}
    if cfg.family == "encdec":
        extras["frames"] = jnp.zeros((args.batch, cfg.enc_seq, cfg.d_model),
                                     jnp.float32)
    if cfg.family == "vlm":
        extras["vision_embeds"] = jnp.zeros(
            (args.batch, cfg.n_patches, cfg.d_model), jnp.float32)

    history = []
    pending = []   # device-side metrics, fetched in batch at the log points

    def _drain():
        """One host sync for the whole pending window.  The step loop
        never blocks on metrics per step (the fetch/format transfer used
        to dominate small-step walltime); everything since the last log
        point converts to floats here in a single transfer burst."""
        for m in pending:
            history.append({k: float(v) for k, v in m.items()})
        pending.clear()

    # graceful pre-emption: the handler only sets a flag; the loop
    # checkpoints on the way down and exits 0 (eviction is not a failure)
    stop = {"sig": None}
    old_handlers = {
        s: signal.signal(s, lambda signum, frame: stop.update(sig=signum))
        for s in (signal.SIGTERM, signal.SIGINT)}

    # rollback ring: (step, host snapshot) of the last K healthy states,
    # refreshed at log points — the only places the host looks at metrics
    # anyway, so the ring adds no extra device syncs
    ring = deque(maxlen=max(args.rollback_ring, 1))
    loss_hist = deque(maxlen=256)   # healthy drained losses (median filter)
    rollbacks = 0

    def _force_degrade(st):
        """Post-rollback: hold every wire domain in its fp32 fallback for
        a full cooldown so the replayed window cannot re-trip on the same
        storm (the rollback+degrade response)."""
        if getattr(st, "guard", None) is None or st.guard.degraded.size == 0:
            return st
        g = dataclasses.replace(
            st.guard, degraded=jnp.ones_like(st.guard.degraded),
            cooldown=jnp.full_like(st.guard.cooldown, args.guard_cooldown))
        return dataclasses.replace(st, guard=g)

    try:
        step = start
        while step < args.steps:
            if stop["sig"] is not None:
                if ckpt:
                    ckpt.save(step, state, meta=data.state(step))
                    ckpt.wait()
                _drain()
                print(f"PREEMPTED: signal {stop['sig']} "
                      f"(checkpointed at step {step}); exiting cleanly",
                      flush=True)
                return history
            batch = {**data.batch(step), **extras}
            t0 = time.time()
            state, metrics = jitted(state, batch)
            if args.step_timeout:
                # the straggler watchdog needs the REAL step walltime, so
                # it opts back into the per-step device sync the deferred
                # metrics path exists to avoid
                jax.block_until_ready(metrics)
            dt = time.time() - t0
            if args.step_timeout and dt > args.step_timeout and step > start:
                raise TimeoutError(
                    f"step {step} took {dt:.1f}s > {args.step_timeout}s "
                    "(straggler watchdog)")
            pending.append(metrics)
            if step % args.log_every == 0 or step == args.steps - 1:
                window_at = len(history)
                _drain()
                window = history[window_at:]
                metrics = history[-1]
                # wire precision domains log alongside the compute triple;
                # per-layer (grouped) wire domains show mean(min-max) so
                # the per-group spread is visible in the train log
                def _wfmt(dom):
                    il, fl = metrics[f"il_{dom}"], metrics[f"fl_{dom}"]
                    if f"il_{dom}_min" in metrics:
                        return (f"<{il:.1f}({metrics[f'il_{dom}_min']:.0f}-"
                                f"{metrics[f'il_{dom}_max']:.0f}),"
                                f"{fl:.1f}({metrics[f'fl_{dom}_min']:.0f}-"
                                f"{metrics[f'fl_{dom}_max']:.0f})> ")
                    return f"<{il:.0f},{fl:.0f}> "

                wire = "".join(
                    tag + _wfmt(dom)
                    for tag, dom in (("wg", "wire_grads"),
                                     ("wp", "wire_params"))
                    if f"il_{dom}" in metrics)
                health = ""
                if metrics.get("health"):
                    from repro.resilience import health_flags
                    health = " !" + ",".join(
                        health_flags(int(metrics["health"])))
                print(f"step {step:5d} loss {metrics['loss']:8.4f} "
                      f"w<{metrics['il_w']:.0f},{metrics['fl_w']:.0f}> "
                      f"a<{metrics['il_a']:.0f},{metrics['fl_a']:.0f}> "
                      f"g<{metrics['il_g']:.0f},{metrics['fl_g']:.0f}> "
                      f"{wire}"
                      f"E_a {metrics['E_a']:.2e} R_a {metrics['R_a']:.2e}"
                      f"{health}", flush=True)
                if args.rollback_ring:
                    losses = [h["loss"] for h in window]
                    bad = any(not np.isfinite(l) for l in losses)
                    med = (float(np.median(loss_hist))
                           if len(loss_hist) >= 4 else None)
                    spiked = bad or (
                        med is not None and med > 0
                        and max(losses) > args.rollback_spike * med)
                    if spiked and ring and rollbacks < 8:
                        snap_step, snap = ring[-1]
                        state = _force_degrade(
                            jax.tree.map(_from_host, snap, state))
                        rollbacks += 1
                        print(f"ROLLBACK: loss spike at step {step} "
                              f"(median {med}), resuming from step "
                              f"{snap_step} with wire degraded", flush=True)
                        step = snap_step
                        continue
                    if not spiked:
                        loss_hist.extend(losses)
                        ring.append(
                            (step + 1, jax.tree.map(_to_host, state)))
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, state, meta=data.state(step + 1))
            if args.fail_at and step + 1 >= args.fail_at:
                raise RuntimeError(f"injected failure at step {step + 1}")
            if (args.sigterm_at and step + 1 >= args.sigterm_at
                    and stop["sig"] is None):
                # pre-emption drill: deliver a real SIGTERM to ourselves;
                # the handler + loop-top path take it from here
                os.kill(os.getpid(), signal.SIGTERM)
            step += 1
    except (TimeoutError, RuntimeError) as e:
        # crash path: persist progress before going down (exit 17 tells
        # the harness this was a FAILURE, unlike the pre-emption exit 0)
        if ckpt:
            ckpt.save(step + 1, state, meta=data.state(step + 1))
            ckpt.wait()
        print(f"ABORT: {e} (checkpointed at step {step + 1})")
        raise SystemExit(17)
    finally:
        for s, h in old_handlers.items():
            signal.signal(s, h)
        if ckpt:
            ckpt.wait()

    if ckpt:
        ckpt.save(args.steps, state, meta=data.state(args.steps))
        ckpt.wait()
    _drain()
    out = {"final_loss": history[-1]["loss"] if history else None,
           "history_tail": history[-5:]}
    print(json.dumps(out, indent=1))
    return history


if __name__ == "__main__":
    main()
