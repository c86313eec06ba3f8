"""ZeRO-1 sharded-optimizer benchmark: replicated vs ZeRO, fp32 vs int8 wire.

Four LeNet train-step variants on a host data mesh:

  * ``replicated_fp32``  — stock data parallelism: implicit fp32 gradient
    all-reduce, optimizer state fully replicated,
  * ``replicated_int8``  — ``grad_allreduce_bits=8``: int8 two-leg gradient
    all-reduce, state still replicated,
  * ``zero_fp32``        — ``zero_opt_shards``: optimizer state sharded
    over the data axis (flat padded layout), exact collective legs,
  * ``zero_int8``        — both: int8 reduce-scatter of gradients + int8
    all-gather of updated parameter shards.

Reported per variant: ring-model wire bytes split int8/fp32 (parsed from
the compiled HLO via ``repro.launch.hlo_stats``), optimizer-state bytes
per device, and walltime per step.  Headline claims: ZeRO cuts per-device
optimizer state to ~1/n, and its int8 schedule moves ≤ ~1/4 the wire bytes
of the fp32 reduce-scatter + all-gather (the ISSUE-3 criterion).

Run standalone (multi-device): ``PYTHONPATH=src python -m
benchmarks.bench_zero`` — the module forces an 8-way host platform before
JAX initializes.  Under ``benchmarks.run`` (JAX already live with one
device) it degrades to a note.
"""

from __future__ import annotations

import os

# only the standalone entry point may mutate process-global XLA flags, and
# only before JAX initializes (see bench_collectives).
if __name__ == "__main__" and "jax" not in __import__("sys").modules:
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))

import time

import jax
import jax.numpy as jnp

from benchmarks.common import is_quick, save_result
from repro.core import qtrain
from repro.core.dps import DPSHyper
from repro.dist.sharding import make_mesh
from repro.launch.hlo_stats import wire_bytes_summary
from repro.models import lenet
from repro.optim import SGDConfig, make_optimizer


def _state_bytes_per_device(state, n_dev: int, zero: bool) -> int:
    """Optimizer-state bytes one device holds (flat ZeRO leaves shard 1/n)."""
    total = sum(l.size * l.dtype.itemsize
                for l in jax.tree.leaves(state.opt_state))
    return total // n_dev if zero else total


def run():
    n_dev = jax.device_count()
    if n_dev < 2:
        out = {"skipped": True,
               "note": "needs a multi-device mesh; run standalone "
                       "(python -m benchmarks.bench_zero)"}
        save_result("zero", out)
        return out

    mesh = make_mesh((n_dev,), ("data",))
    opt = make_optimizer(SGDConfig())
    params = lenet.init(jax.random.key(0))
    batch_n = 64 if is_quick() else 512
    iters = 3 if is_quick() else 20
    batch = {"images": jax.random.normal(jax.random.key(2),
                                         (batch_n, 28, 28, 1)) * 0.5,
             "labels": jax.random.randint(jax.random.key(3), (batch_n,),
                                          0, 10)}
    # static formats sized to the init stats so the int8 legs don't clip
    base = dict(enabled=False, controller="static",
                hyper_grads=DPSHyper(il_init=6, fl_init=2),
                hyper_weights=DPSHyper(il_init=2, fl_init=14))

    variants = {
        "replicated_fp32": qtrain.QuantConfig(**base),
        "replicated_int8": qtrain.QuantConfig(**base, grad_allreduce_bits=8),
        "zero_fp32": qtrain.QuantConfig(**base, zero_opt_shards=n_dev),
        "zero_int8": qtrain.QuantConfig(**base, grad_allreduce_bits=8,
                                        zero_opt_shards=n_dev),
    }

    results = {}
    for name, qcfg in variants.items():
        zero = qtrain.zero_opt_engaged(qcfg, mesh)
        step = qtrain.make_train_step(lenet.loss_fn, opt, qcfg, mesh=mesh)
        opt_state = (qtrain.zero_opt_state(opt, params, n_dev) if zero
                     else opt.init(params))
        state = qtrain.TrainState.create(params, opt_state, qcfg,
                                         jax.random.key(1))
        if name == "replicated_fp32":
            # stock DP needs the batch sharded for the implicit all-reduce
            # to appear in HLO; the shard_map variants pin specs themselves
            from jax.sharding import NamedSharding, PartitionSpec as P
            repl = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
            bsh = {k: NamedSharding(mesh, P("data")) for k in batch}
            jitted = jax.jit(step, in_shardings=(repl, bsh),
                             out_shardings=None)
        else:
            jitted = jax.jit(step)
        wire = wire_bytes_summary(
            jitted.lower(state, batch).compile().as_text())

        s, _ = jitted(state, batch)             # compile + warm
        jax.block_until_ready(s)
        t0 = time.time()
        for _ in range(iters):
            s, _ = jitted(s, batch)
        jax.block_until_ready(s)
        results[name] = {
            "wire_bytes": wire,
            "opt_state_bytes_per_device":
                _state_bytes_per_device(state, n_dev, zero),
            "ms_per_step": (time.time() - t0) / iters * 1e3,
            "wire_sync_active": bool(step.wire_sync_active),
            "zero_opt_active": bool(step.zero_opt_active),
        }

    # fp32 baseline for the headline ratio: the same reduce-scatter +
    # all-gather schedule without the codec, over the same padded flat size
    # (zero_fp32's own gradient leg is GSPMD's implicit all-reduce, a
    # different schedule — see dist/README.md).
    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import ZeroPartitioner
    part = ZeroPartitioner.create(params, n_dev)

    def _fp32_ref(x):
        s = jax.lax.psum_scatter(x.reshape(n_dev, part.shard_size), "data",
                                 scatter_dimension=0, tiled=True)
        return jax.lax.all_gather(s, "data", axis=0, tiled=True)

    ref = jax.jit(jax.shard_map(_fp32_ref, mesh=mesh, in_specs=P(),
                                out_specs=P(), check_vma=False))
    fp32_ref = wire_bytes_summary(
        ref.lower(jax.ShapeDtypeStruct((part.padded_size,), jnp.float32)
                  ).compile().as_text())["fp32"]

    zi, zf = results["zero_int8"], results["zero_fp32"]
    rep = results["replicated_fp32"]
    wire_ratio = (zi["wire_bytes"]["int8"] / fp32_ref) if fp32_ref else None
    out = {
        "n_devices": n_dev,
        "per_variant": results,
        "fp32_reduce_scatter_allgather_wire_bytes": fp32_ref,
        "zero_int8_over_fp32_schedule_wire_ratio": wire_ratio,
        "opt_state_shrink":
            rep["opt_state_bytes_per_device"]
            / max(zi["opt_state_bytes_per_device"], 1),
        "note": "CPU container: walltime is emulation cost, not a fabric "
                "measurement; wire bytes are ring-model from compiled HLO",
        "claims": {
            "zero_int8_wire_le_quarter_fp32":
                wire_ratio is not None and wire_ratio <= 0.26,
            "zero_shards_opt_state":
                zi["opt_state_bytes_per_device"]
                <= rep["opt_state_bytes_per_device"] // n_dev + 8,
            "all_paths_engaged":
                zi["zero_opt_active"] and zi["wire_sync_active"]
                and zf["zero_opt_active"]
                and results["replicated_int8"]["wire_sync_active"],
        },
    }
    save_result("zero", out)
    return out


if __name__ == "__main__":
    import json
    print(json.dumps(run(), indent=1, default=float))
