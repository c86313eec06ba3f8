"""The DPS tree passes' fused quantize kernel and when the train step
engages it.

Off a TPU ``ops.dps_quantize_leaf`` interprets the kernel with the bits
operand that ``fixed_point.quantize`` draws from the same key, so the
fused tree path must reproduce the jnp path value for value, and its
stats to f32 summation order.  The train step engages the kernel only on
a TPU with no mesh or a one-device mesh (``fused_quant_active``); a
multi-device mesh keeps the jnp path, bit for bit.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fixed_point as fxp
from repro.core import qtrain
from repro.core.policy import QuantPolicy
from repro.dist import make_mesh
from repro.kernels import ops
from repro.models import lenet
from repro.optim import SGDConfig, make_optimizer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the sums to f32 summation order; counts and the max exactly
SUM_FIELDS = ("abs_err_sum", "rel_err_sum", "abs_sum")
EXACT_FIELDS = ("count", "nonzero", "overflow", "max_abs")


def _tree():
    k = jax.random.split(jax.random.key(0), 4)
    return {
        # rows and columns off the kernel's blocks
        "embed": jax.random.normal(k[0], (1157, 600)) * 3,
        "layers": {
            # layer-stacked: quantize_tree maps over the 5 layers
            "w": jax.random.normal(k[1], (5, 840, 1000)) * 3,
            "norm1": 1 + jax.random.normal(k[2], (5, 1000)) * 0.1,
        },
        "head": (jax.random.normal(k[3], (3, 64, 256)) * 3
                 ).astype(jnp.bfloat16),
    }


@pytest.mark.parametrize("mode", [fxp.ROUND_STOCHASTIC, fxp.ROUND_NEAREST])
def test_fused_tree_path_matches_quantize_tree(mode):
    tree = _tree()
    w = tree["layers"]["w"]
    assert w.ndim >= 3 and w.shape[0] > 4 and w.size > (1 << 22)
    fmt = fxp.FixedPointFormat.create(4, 7)      # |x| > 8 overflows
    pred = QuantPolicy().param_predicate()
    key = jax.random.key(3)
    q_j, s_j = fxp.quantize_tree(tree, fmt, mode=mode, key=key,
                                 predicate=pred)
    q_f, s_f = fxp.quantize_tree(tree, fmt, mode=mode, key=key,
                                 predicate=pred,
                                 quantize_fn=ops.dps_quantize_leaf)
    for a, b in zip(jax.tree.leaves(q_j), jax.tree.leaves(q_f)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
    assert q_f["layers"]["norm1"] is tree["layers"]["norm1"]
    assert float(s_j.overflow) > 0
    assert float(s_f.count) == sum(x.size for x in (
        tree["embed"], w, tree["head"]))
    for f in EXACT_FIELDS:
        assert float(getattr(s_f, f)) == float(getattr(s_j, f)), f
    for f in SUM_FIELDS:
        np.testing.assert_allclose(float(getattr(s_f, f)),
                                   float(getattr(s_j, f)), rtol=1e-5,
                                   err_msg=f)


def _lenet_setup():
    opt = make_optimizer(SGDConfig(lr=0.0078125, momentum=0.5,
                                   schedule="const"))
    qcfg = qtrain.QuantConfig(enabled=True)
    params = lenet.init(jax.random.key(0))
    batch = {"images": jax.random.normal(jax.random.key(2), (8, 28, 28, 1)),
             "labels": jax.random.randint(jax.random.key(3), (8,), 0, 10)}
    state = qtrain.TrainState.create(params, opt.init(params), qcfg,
                                     jax.random.key(1))
    return opt, qcfg, state, batch


def test_fused_quant_engages_on_a_tpu_with_at_most_one_device(monkeypatch):
    opt, qcfg, _, _ = _lenet_setup()
    make = lambda mesh=None: qtrain.make_train_step(lenet.loss_fn, opt, qcfg,
                                                    mesh=mesh)
    assert make().fused_quant_active is False          # off TPU
    monkeypatch.setattr(qtrain, "on_tpu", lambda: True)
    assert make().fused_quant_active is True
    assert make(make_mesh((1,), ("data",))).fused_quant_active is True


def test_fused_step_matches_jnp_step(monkeypatch):
    """The step with the fused tree passes (interpreted, bits operand)
    moves the weights and the controllers exactly as the jnp step."""
    opt, qcfg, state, batch = _lenet_setup()
    jnp_step = jax.jit(qtrain.make_train_step(lenet.loss_fn, opt, qcfg))
    monkeypatch.setattr(qtrain, "on_tpu", lambda: True)
    fused = qtrain.make_train_step(lenet.loss_fn, opt, qcfg)
    assert fused.fused_quant_active
    fused_step = jax.jit(fused)
    s_j = s_f = state
    for i in range(2):
        s_j, m_j = jnp_step(s_j, batch)
        s_f, m_f = fused_step(s_f, batch)
        assert float(m_j["loss"]) == float(m_f["loss"]), i
    for a, b in zip(jax.tree.leaves(s_j.params), jax.tree.leaves(s_f.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(s_j.dps), jax.tree.leaves(s_f.dps)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_quant_stays_off_under_a_multi_device_mesh():
    """Under a 2-device data mesh the step keeps the jnp path even on a
    TPU: the same trajectory, bit for bit, as the step built off TPU."""
    code = textwrap.dedent("""
        import jax, numpy as np
        from repro.core import qtrain
        from repro.dist import make_mesh
        from repro.models import lenet
        from repro.optim import SGDConfig, make_optimizer

        mesh = make_mesh((2,), ("data",))
        opt = make_optimizer(SGDConfig(lr=0.0078125, momentum=0.5,
                                       schedule="const"))
        qcfg = qtrain.QuantConfig(enabled=True)
        params = lenet.init(jax.random.key(0))
        batch = {"images": jax.random.normal(jax.random.key(2),
                                             (8, 28, 28, 1)),
                 "labels": jax.random.randint(jax.random.key(3), (8,),
                                              0, 10)}
        state = qtrain.TrainState.create(params, opt.init(params), qcfg,
                                         jax.random.key(1))
        ref = qtrain.make_train_step(lenet.loss_fn, opt, qcfg, mesh=mesh)
        qtrain.on_tpu = lambda: True
        step = qtrain.make_train_step(lenet.loss_fn, opt, qcfg, mesh=mesh)
        assert not step.fused_quant_active
        s_r = s_t = state
        for _ in range(2):
            s_r, _ = jax.jit(ref)(s_r, batch)
            s_t, _ = jax.jit(step)(s_t, batch)
        for a, b in zip(jax.tree.leaves((s_r.params, s_r.dps)),
                        jax.tree.leaves((s_t.params, s_t.dps))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        print("OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert out.stdout.strip().endswith("OK")
