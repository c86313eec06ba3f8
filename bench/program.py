"""The system under test, built the way its training CLI builds it.

Everything the benchmark takes from the program is here: the model
configuration type, the DPS and optimizer settings, the mesh, and the
jitted train step of ``repro.launch.train.build``.  The weights, batches
and the reference are the benchmark's own.
"""

from __future__ import annotations

import dataclasses
import importlib
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import inputs
from .spec import Cell


def reference_model(cell: Cell):
    return importlib.import_module(
        f"bench.reference.{cell.config['reference']}")


@dataclasses.dataclass
class Program:
    cell: Cell
    cfg: Any                      # repro.configs.base.ModelConfig
    step: Callable                # jitted (state, batch) -> (state, metrics)
    make_state: Callable          # jitted key -> TrainState
    make_batch: Callable          # jitted (key, step) -> batch
    specs: list                   # [(path, shape, dtype, init, scale)]


def model_config(cell: Cell, smoke: bool = False):
    from repro.configs.base import ModelConfig, smoke as smoke_cfg
    cfg = ModelConfig(**cell.config["model"])
    return smoke_cfg(cfg) if smoke else cfg


def quant_config(cell: Cell, cfg):
    from repro.core import dps, qtrain
    from repro.core.policy import QuantPolicy
    from repro.launch import specs as specs_lib

    d = cell.traffic["dps"]
    hyper = lambda dom: dps.DPSHyper(**d[dom])
    qcfg = qtrain.QuantConfig(
        enabled=d["enabled"], controller=d["controller"],
        rounding="stochastic",
        policy=QuantPolicy(exclude=tuple(d["exclude"])),
        hyper_weights=hyper("weights"), hyper_acts=hyper("acts"),
        hyper_grads=hyper("grads"),
        grad_allreduce_bits=cell.traffic.get("grad_allreduce_bits"),
        wire_controller=cell.traffic.get("wire_controller", "flexpoint"))
    if cell.traffic.get("wire_groups") == "per-layer":
        qcfg = specs_lib.per_layer_wire_qcfg(cfg, qcfg)
    return qcfg


def optimizer_config(cell: Cell):
    from repro.optim import AdamWConfig
    o = cell.traffic["optimizer"]
    return AdamWConfig(lr=o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"],
                       weight_decay=o["weight_decay"], warmup=o["warmup"],
                       total_steps=o["total_steps"],
                       clip_norm=o["clip_norm"])


def model_dict(cfg) -> dict:
    return dataclasses.asdict(cfg)


def check_layout(cfg, specs) -> None:
    """The program's parameter tree has exactly the benchmark's leaves."""
    from repro.models import registry
    from repro.models.common import abstract_params
    tree = abstract_params(registry(cfg.family).model_defs(cfg))
    have = {"/".join(str(k.key) for k in path): (tuple(v.shape),
                                                 jnp.dtype(v.dtype).name)
            for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]}
    want = {p: (tuple(s), jnp.dtype(d).name) for p, s, d, _, _ in specs}
    if have != want:
        raise SystemExit(
            "the program's parameter layout differs from the benchmark's: "
            f"program {sorted(set(have.items()) - set(want.items()))}, "
            f"benchmark {sorted(set(want.items()) - set(have.items()))}")


def build(cell: Cell, devices, smoke: bool = False) -> Program:
    """The program for ``cell`` on ``devices``; ``smoke`` cuts the model to
    its smoke widths (give it a ``spec.smoke`` cell too)."""
    from repro.core import qtrain
    from repro.dist import make_mesh
    from repro.launch import specs as specs_lib
    from repro.launch import train as train_cli

    cfg = model_config(cell, smoke)
    specs = reference_model(cell).param_specs(model_dict(cfg))
    check_layout(cfg, specs)
    qcfg = quant_config(cell, cfg)
    mesh = None
    if len(devices) > 1:
        mesh = make_mesh((len(devices),), ("data",), devices=devices)
    opt, step = train_cli.build(cfg, qcfg, optimizer_config(cell), mesh=mesh)
    if qcfg.grad_allreduce_bits is not None and not step.wire_sync_active:
        raise SystemExit("the int8 gradient wire did not engage")

    def state_fn(key):
        params = inputs.nest(inputs.weights(jax.random.fold_in(key, 0),
                                            specs))
        return qtrain.TrainState.create(params, opt.init(params), qcfg,
                                        jax.random.fold_in(key, 1))

    cfg_d = model_dict(cfg)
    batch_fn = lambda key, i: inputs.batch(key, i, cell.traffic, cfg_d)
    if mesh is None:
        dev = devices[0]
        make_state = jax.jit(state_fn, out_shardings=jax.sharding
                             .SingleDeviceSharding(dev))
        make_batch = jax.jit(batch_fn, out_shardings=jax.sharding
                             .SingleDeviceSharding(dev))
    else:
        from repro.dist.sharding import DEFAULT_RULES, LogicalRules
        rules = LogicalRules(rules=tuple(r for r in DEFAULT_RULES
                                         if r[0] != "fsdp"))
        state_sh = specs_lib.train_state_shardings(cfg, mesh, rules, opt,
                                                   qcfg)
        make_state = jax.jit(state_fn, out_shardings=state_sh)
        make_batch = jax.jit(batch_fn,
                             out_shardings=NamedSharding(mesh, P("data")))
    return Program(cell=cell, cfg=cfg, step=step, make_state=make_state,
                   make_batch=make_batch, specs=specs)
