"""The SSD scan's intra-chunk kernels (``kernels/ssd.py``), run by the
Pallas interpreter on the CPU, against the jnp form of ``models/ssm.py``
``_ssd_chunks``: values and gradients with respect to x·dt, B, C and the
log-decays, at chunks of 128 and 256, states of 64 and 128, two head
blocks, a chunk's summed decay past exp's float32 overflow, a carried
state and a padded length; and which path ``ssd_scan`` takes where."""

import dataclasses
import functools
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, smoke
from repro.models import registry, ssm
from repro.models.common import init_params

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OVERFLOW = 88.7          # log of float32's largest value
# As tests/test_ssd_scan.py: float32 sums in other orders, 1e-4 of the
# largest value (20x under one bfloat16 rounding of the operands).
RTOL = 1e-4
# The chip's operands, rounded to bfloat16 as XLA rounds the jnp form's on
# a TPU, against the float32 form: chip_smoke.py's SSD_RTOL.
BF16_RTOL = 2e-2

# the interpreter with float32 operands: the jnp form's algorithm exactly
KERNEL_F32 = functools.partial(ssm.ssd_intra, dot_dtype=jnp.float32)
# with the chip's bfloat16 operands
KERNEL_BF16 = ssm.ssd_intra


def _inputs(B, S, H, P, N, seed, h0=False):
    kx, kb, kc, kd, ka, kh = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(kx, (B, S, H, P))
    b = jax.random.normal(kb, (B, S, N))
    c = jax.random.normal(kc, (B, S, N))
    # dt > 1 on average and |A| in (0.74, 1.35): a chunk of 128 steps
    # sums its log-decays past the overflow
    dt = jax.nn.softplus(jax.random.normal(kd, (B, S, H)) + 1.0)
    a = -jnp.exp(jax.random.uniform(ka, (H,), minval=-0.3, maxval=0.3))
    state = jax.random.normal(kh, (B, H, P, N)) if h0 else None
    return (x, b, c, dt, dt * a), state


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got), np.asarray(want)
    assert np.all(np.isfinite(got))
    gap = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert gap <= rtol, gap


def _scan(cfg, intra, monkeypatch):
    """``ssd_scan`` with the intra-chunk form given: ``intra`` None keeps
    the jnp form, else the kernel path runs with ``intra`` as its call."""
    def run(x, b, c, dt, ld, h0):
        with monkeypatch.context() as m:
            if intra is not None:
                m.setattr(ssm, "intra_kernel_fits", lambda *_: True)
                m.setattr(ssm, "ssd_intra", intra)
            return ssm.ssd_scan(cfg, x, b, c, dt, ld, h0)
    return run


def _value_and_grads(fn, args, h0, w):
    """y, h_final, and the gradients of <y, w> + <h_final, 1> with respect
    to x, B, C, dt and the log-decays."""
    def loss(*xs):
        y, h = fn(*xs, h0)
        return jnp.sum(y * w) + jnp.sum(h)
    y, h = jax.jit(lambda *xs: fn(*xs, h0))(*args)
    return (y, h, *jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*args))


CASES = {
    # name: (B, S, H, P, N, Q, carried state); heads in blocks of 8
    "q128-n64-two-head-blocks": (2, 384, 16, 8, 64, 128, False),
    "q256-n128-two-head-blocks": (1, 512, 16, 8, 128, 256, False),
    "q256-n64-padded-carried-state": (2, 300, 16, 8, 64, 256, True),
    "q128-n128-one-head-block": (1, 256, 8, 16, 128, 128, True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_kernels_match_the_jnp_form(case, monkeypatch):
    B, S, H, P, N, Q, h0 = CASES[case]
    cfg = dataclasses.replace(smoke(get_config("mamba2_1_3b")), ssm_chunk=Q)
    args, state = _inputs(B, S, H, P, N, seed=S + H + N, h0=h0)
    summed = -args[4][:, :Q].sum(axis=1)                 # one chunk
    assert float(summed.min()) > OVERFLOW
    w = jax.random.normal(jax.random.key(7), (B, S, H, P))
    want = _value_and_grads(_scan(cfg, None, monkeypatch), args, state, w)
    got = _value_and_grads(_scan(cfg, KERNEL_F32, monkeypatch), args,
                           state, w)
    for g, g_o in zip(got, want):
        _close(g, g_o)


def test_kernels_with_the_chips_bf16_operands(monkeypatch):
    """The configuration the chip runs: x, B and C in bfloat16 as the model
    gives them, every dot's operands rounded to bfloat16."""
    B, S, H, P, N, Q = 1, 512, 16, 8, 64, 256
    cfg = dataclasses.replace(smoke(get_config("mamba2_1_3b")), ssm_chunk=Q)
    (x, b, c, dt, ld), _ = _inputs(B, S, H, P, N, seed=11)
    args = (x.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
            c.astype(jnp.bfloat16), dt, ld)
    w = jax.random.normal(jax.random.key(7), (B, S, H, P))
    as_f32 = lambda fn: lambda *xs: tuple(
        t.astype(jnp.float32) for t in fn(*xs))
    want = _value_and_grads(as_f32(_scan(cfg, None, monkeypatch)), args,
                            None, w)
    got = _value_and_grads(as_f32(_scan(cfg, KERNEL_BF16, monkeypatch)),
                           args, None, w)
    for g, g_o in zip(got, want):
        assert g.dtype == g_o.dtype
        _close(g.astype(jnp.float32), g_o.astype(jnp.float32), BF16_RTOL)


def test_mamba2_model_runs_the_kernels(monkeypatch):
    """The ssm family's smoke model at chunk 256 over a padded length: its
    loss and gradients on the kernel path match the jnp form's."""
    cfg = dataclasses.replace(smoke(get_config("mamba2_1_3b")), ssm_chunk=256)
    mod = registry(cfg.family)
    params = init_params(jax.random.key(0), mod.model_defs(cfg))
    toks = jax.random.randint(jax.random.key(1), (2, 301), 0, cfg.vocab)

    def loss_and_grads():
        return jax.jit(jax.value_and_grad(
            lambda p: mod.loss_fn(cfg)(p, {"tokens": toks})[0]))(params)

    want = loss_and_grads()
    monkeypatch.setattr(ssm, "intra_kernel_fits", lambda *_: True)
    monkeypatch.setattr(ssm, "ssd_intra", KERNEL_F32)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda p: mod.loss_fn(cfg)(p, {"tokens": toks})[0]))(params))
    assert "ssd_intra_fwd" in jaxpr and "ssd_intra_bwd" in jaxpr
    got = loss_and_grads()
    for g, g_o in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        _close(g, g_o)


def _kernel_in_jaxpr(Q, H, P, N=16, S=256):
    cfg = dataclasses.replace(smoke(get_config("mamba2_1_3b")), ssm_chunk=Q)
    (x, b, c, dt, ld), _ = _inputs(1, S, H, P, N, seed=0)
    jaxpr = jax.make_jaxpr(
        lambda *a: ssm.ssd_scan(cfg, *a)[0])(x, b, c, dt, ld)
    return "pallas_call" in str(jaxpr)


def test_the_cpu_runs_the_jnp_form():
    assert not ssm.intra_kernel_fits(256, 64, 64)
    assert not _kernel_in_jaxpr(256, 8, 16)


def test_a_tpu_runs_the_kernels_only_where_they_tile(monkeypatch):
    monkeypatch.setattr(ssm, "on_tpu", lambda: True)
    assert _kernel_in_jaxpr(256, 8, 16)           # 8 heads of 16: 128 lanes
    assert _kernel_in_jaxpr(128, 64, 64)
    assert not _kernel_in_jaxpr(8, 8, 16)         # the smoke chunk
    assert not _kernel_in_jaxpr(256, 8, 8)        # 8 rows a head
    assert not _kernel_in_jaxpr(256, 12, 16)      # heads not in blocks of 8
    smoke_cfg = smoke(get_config("mamba2_1_3b"))
    assert smoke_cfg.ssm_chunk == 8
    assert not ssm.intra_kernel_fits(smoke_cfg.ssm_chunk,
                                     ssm.n_ssm_heads(smoke_cfg),
                                     smoke_cfg.ssm_head_dim)


def test_a_multi_device_mesh_runs_the_jnp_form():
    code = textwrap.dedent("""
        import jax
        from repro.dist import make_mesh
        from repro.dist.sharding import DEFAULT_RULES, LogicalRules, axis_rules
        from repro.models import ssm
        ssm.on_tpu = lambda: True
        rules = LogicalRules(rules=DEFAULT_RULES)
        with axis_rules(make_mesh((2,), ("data",)), rules):
            assert not ssm.intra_kernel_fits(256, 64, 64)
        with axis_rules(make_mesh((1,), ("data",),
                                  devices=jax.devices()[:1]), rules):
            assert ssm.intra_kernel_fits(256, 64, 64)
        assert ssm.intra_kernel_fits(256, 64, 64)
        print("OK")
    """)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert out.stdout.strip().endswith("OK")
