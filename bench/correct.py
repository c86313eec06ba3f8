"""What decides ``correct``: the program's first three steps against the
plain reference's three steps from the same seed.

Three numbers are compared, each with its limit from ``limits/<cell>.json``:

- ``loss_gap``: the largest relative gap between the program's and the
  reference's loss over the three steps;
- ``grad_norm_gap``: the worst leaf's gap between the norms of the first
  gradient as AdamW received it (its first moment after one step, over
  1 - b1), measured against the larger of that leaf's reference norm and
  the median leaf's;
- ``delta_norm_gap``: the same for the parameters' change over three steps,
  over the leaves that the reference's first gradient moves (norm at least
  a thousandth of the median leaf's): a leaf whose gradient is nought to
  rounding moves under Adam by round-off alone.
"""

from __future__ import annotations

import statistics
from typing import Dict

import jax
import jax.numpy as jnp

from . import inputs
from .reference import dps as ref_dps

STEPS = 3
STILL = 1e-3       # leaves whose gradient is under this share of the median
BATCH_STREAM = 2   # batches come from fold_in(seed key, BATCH_STREAM)


def _flat_norms(tree) -> Dict[str, jax.Array]:
    return {"/".join(str(k.key) for k in path):
            jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
            for path, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def delta_norms_fn(specs):
    """Jitted ``(params, key) -> {path: norm of params - the seed's
    weights}``; the weights are made again inside, so no copy is kept."""
    return jax.jit(lambda params, key: _flat_norms(jax.tree.map(
        lambda p, w: p.astype(jnp.float32) - w.astype(jnp.float32), params,
        inputs.nest(inputs.weights(jax.random.fold_in(key, 0), specs)))))


def program_readings(prog, key, fault: str = "none"):
    """Drive the program's own step and feed from the seed through the
    first ``STEPS`` steps, with no host sync between them.  Returns the state
    (for the window to continue from) and the readings.

    ``fault="half"`` plants half of the batch left out: a loss mask keeps
    the first half of the rows (of the positions, for a single row), and
    the loss is the mean over the rest.  ``fault="unchanged"`` plants a step
    that returns its state unchanged."""
    if fault not in ("none", "half", "unchanged"):
        raise ValueError(f"no program fault {fault!r}")
    step = prog.step
    if fault == "unchanged":
        inner = prog.step.__wrapped__
        step = jax.jit(lambda s, b: (s, inner(s, b)[1]))
    b1 = prog.cell.traffic["optimizer"]["b1"]
    m_norms = jax.jit(lambda st: {k: v / (1 - b1) for k, v in
                                  _flat_norms(st.opt_state["m"]).items()})
    bkey = jax.random.fold_in(key, BATCH_STREAM)
    state = prog.make_state(key)
    losses = []
    for i in range(STEPS):
        batch = prog.make_batch(bkey, i)
        if fault == "half":
            batch = dict(batch, loss_mask=_half_mask(batch["tokens"]))
        state, metrics = step(state, batch)
        losses.append(metrics["loss"])
        if i == 0:
            grad_norms = m_norms(state)
    delta = delta_norms_fn(prog.specs)(state.params, key)
    return state, {"losses": [float(x) for x in losses],
                   "grad_norms": {k: float(v) for k, v in grad_norms.items()},
                   "delta_norms": {k: float(v) for k, v in delta.items()}}


def _half_mask(tokens: jax.Array) -> jax.Array:
    rows, cols = tokens.shape[0], tokens.shape[1] - 1
    if rows > 1:
        keep = jnp.arange(rows)[:, None] < rows // 2
        return jnp.broadcast_to(keep, (rows, cols)).astype(jnp.float32)
    return (jnp.arange(cols)[None, :] < cols // 2).astype(jnp.float32)


REF_STREAM, CONTROL_STREAM = 0x5EF, 0xC0E


def reference_readings(cell, cfg_d: dict, key, precision: str = "f32",
                       device=None, stream: int = REF_STREAM) -> dict:
    """Three steps of the plain reference from the seed's weights and rows.

    ``stream`` keys its rounding noise.  ``precision="fp8"``, on a stream of
    its own, is the control."""
    from .program import reference_model

    model = reference_model(cell)
    specs = model.param_specs(cfg_d)
    t, d = cell.traffic, cell.traffic["dps"]
    hypers = ref_dps.Domains(*(ref_dps.Hyper(**d[k])
                               for k in ("weights", "acts", "grads")))
    step = ref_dps.make_step(model.loss_fn(cfg_d), t["optimizer"],
                             d["exclude"], precision, d["enabled"])
    dev = device or jax.devices()[0]
    with jax.default_device(dev):
        params = jax.jit(lambda k: inputs.weights(jax.random.fold_in(k, 0),
                                                  specs))(key)
        batch = jax.jit(lambda k, i: inputs.batch(k, i, t, cfg_d))
        bkey = jax.random.fold_in(key, BATCH_STREAM)
        batches = [batch(bkey, i) for i in range(STEPS)]
        out = ref_dps.run_three_steps(step, params, batches, hypers,
                                      t["optimizer"],
                                      jax.random.fold_in(key, stream))
        delta = delta_norms_fn(specs)(inputs.nest(out.pop("params")), key)
    out["delta_norms"] = {k: float(v) for k, v in delta.items()}
    return out


def compare(prog: dict, ref: dict) -> dict:
    """The three compared numbers, and the leaf behind each norm gap."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(prog["losses"], ref["losses"]))

    def worst(p: dict, r: dict, leaves):
        med = statistics.median(r[k] for k in leaves)
        gaps = {k: abs(p[k] - r[k]) / max(r[k], med) for k in leaves}
        k = max(gaps, key=gaps.get)
        return gaps[k], k

    gr = ref["grad_norms"]
    med_g = statistics.median(gr.values())
    g_gap, g_leaf = worst(prog["grad_norms"], gr, sorted(gr))
    moving = sorted(k for k in gr if gr[k] >= STILL * med_g)
    d_gap, d_leaf = worst(prog["delta_norms"], ref["delta_norms"], moving)
    return {"loss_gap": loss_gap, "grad_norm_gap": g_gap,
            "delta_norm_gap": d_gap, "grad_leaf": g_leaf,
            "delta_leaf": d_leaf}


def judge(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)


def report(numbers: dict, limits: dict) -> Dict[str, list]:
    """{name: [number, limit]} for the result line and standard error."""
    return {k: [numbers[k], limits[k]] for k in limits}
