"""Counts from shapes: parameters, model FLOPs, executed matmul FLOPs."""

import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from bench import flops, spec

CONFIGS = {"internvl2-26b-l1": "train-dps-4k",
           "mamba2-1.3b-l16": "train-dps-4x2k"}


def smoke_cell(config: str) -> spec.Cell:
    """A one-chip cell of ``config`` under its training mix, at the
    mix's smoke sizes."""
    load = lambda d, n: json.load(open(spec.BENCH / d / f"{n}.json"))
    traffic = load("traffic", CONFIGS[config])
    traffic.update(traffic["smoke"])
    return spec.Cell(name=config, chips=1, config=load("configs", config),
                     traffic=traffic, limits={}, end_to_end=[], per_layer=[])


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_param_count_matches_the_program(config):
    from bench import program
    cfg = program.model_config(smoke_cell(config))
    assert flops.param_count(dataclasses.asdict(cfg)) == cfg.n_params()


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_config_file_is_the_published_config_cut_as_listed(config):
    from repro.configs.base import get_config
    from bench import program
    cell = smoke_cell(config)
    pub = dataclasses.asdict(get_config(cell.config["model"]["name"]))
    run = dataclasses.asdict(program.model_config(cell))
    changed = {k for k in pub if pub[k] != run[k]}
    assert changed == set(cell.config["reduced"])
    for k, v in cell.config["reduced"].items():
        assert (pub[k], run[k]) == (v["published"], v["run"])


M, K, N = 8, 16, 32


def _mm(a, b):
    return a @ b


def test_jaxpr_matmul_flops_counts_dots_scans_and_recompute():
    x, w = jnp.ones((M, K)), jnp.ones((K, N))
    one = 2 * M * K * N
    assert flops.jaxpr_matmul_flops(jax.make_jaxpr(_mm)(x, w)) == one

    def scanned(w):
        body = lambda c, _: (c, jnp.sum(x @ w))
        return jax.lax.scan(body, 0.0, None, length=3)[1].sum()

    assert flops.jaxpr_matmul_flops(jax.make_jaxpr(scanned)(w)) == 3 * one
    # forward, recomputed forward, and the two backward products
    f = jax.checkpoint(lambda a, b: jnp.sum(jnp.tanh(a @ b)))
    j = jax.make_jaxpr(jax.jit(jax.value_and_grad(f, argnums=(0, 1))))(x, w)
    assert flops.jaxpr_matmul_flops(j) == 4 * one


@pytest.mark.parametrize("config", sorted(CONFIGS))
def test_executed_matmul_flops_of_the_smoke_step_cover_the_model(config):
    """The step executes at least the model's matmuls.  At smoke widths
    it executes 1.36x (Mamba2) to 1.55x (InternVL2) of them: the head's
    checkpointed chunk runs twice over a vocabulary padded 256 -> 512, and
    attention and the SSD chunks run their full squares; layer remat is
    off there.  Twice the model's count leaves room for that and would
    catch a matmul counted twice over."""
    from bench import program
    cell = smoke_cell(config)
    prog = program.build(cell, jax.devices()[:1], smoke=True)
    key = jax.random.key(0)
    state = jax.eval_shape(prog.make_state, key)
    batch = jax.eval_shape(prog.make_batch, key, 0)
    executed = flops.jaxpr_matmul_flops(
        jax.make_jaxpr(prog.step)(state, batch))
    t = cell.traffic
    model = flops.model_flops_per_step(program.model_dict(prog.cfg),
                                       t["global_batch"], t["seq"])
    assert model <= executed <= 2 * model
