"""The SSD chunked scan (``models/ssm.py`` ``ssd_scan``) at the published
chunk of 256 with a chunk's summed decay far past 88.7, where exp of a
positive segment sum overflows float32: its values and its gradients are
finite and agree with the plain reference (``bench/reference/mamba2.py``
``ssd``) and with the step-by-step recurrence, on the padded-length branch,
from a carried state, and inside the hybrid family's model."""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, smoke
from repro.models import registry, ssm
from repro.models.common import init_params

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
from bench.reference import mamba2 as ref  # noqa: E402

Q = 256
B, H, P, N = 2, 3, 4, 8
CFG = dataclasses.replace(smoke(get_config("mamba2_1_3b")), ssm_chunk=Q)
HIGHEST = jax.lax.Precision.HIGHEST
OVERFLOW = 88.7          # log of float32's largest value

# Every side sums in float32, in different orders: one chunk's 256 masked
# products and then the recurrence over chunks, against one product per
# step.  The largest gap seen here is 3.3e-5 of the largest value (the
# hybrid model's logits, through two layers; 1.4e-5 for the scan's values
# and gradients alone); 1e-4 leaves 3x room and is 20x under one bfloat16
# rounding (2^-9) of the operands.
RTOL = 1e-4


def _inputs(S: int, seed: int, h0: bool):
    kx, kb, kc, kd, ka, kh = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(kx, (B, S, H, P))
    b = jax.random.normal(kb, (B, S, N))
    c = jax.random.normal(kc, (B, S, N))
    dt = jax.nn.softplus(jax.random.normal(kd, (B, S, H)))
    a = -jnp.exp(jax.random.uniform(ka, (H,), minval=-0.3, maxval=0.3))
    state = jax.random.normal(kh, (B, H, P, N)) if h0 else None
    return (x, b, c, dt), a, state


def _program(x, b, c, dt, a, h0=None):
    return ssm.ssd_scan(CFG, x, b, c, dt, dt * a, h0)


def _recurrence(x, b, c, dt, a, h0=None):
    """h_t = exp(dt_t a) h_{t-1} + dt_t x_t B_t^T,  y_t = C_t h_t."""
    if h0 is None:
        h0 = jnp.zeros((B, H, P, N))

    def step(h, inp):
        xt, bt, ct, dtt = inp
        h = (jnp.exp(dtt * a)[..., None, None] * h
             + jnp.einsum("bhp,bn->bhpn", xt * dtt[..., None], bt,
                          precision=HIGHEST))
        return h, jnp.einsum("bn,bhpn->bhp", ct, h, precision=HIGHEST)

    h, y = jax.lax.scan(step, h0, tuple(jnp.moveaxis(t, 1, 0)
                                        for t in (x, b, c, dt)))
    return jnp.moveaxis(y, 0, 1), h


def _reference(x, b, c, dt, a, h0=None):
    mm = lambda s, *ops: jnp.einsum(s, *ops, precision=HIGHEST)
    return ref.ssd(x, dt, a, b, c, Q, mm), None


def _close(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert np.all(np.isfinite(got))
    gap = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert gap <= RTOL, gap


def _grads(fn, args, a, h0, w):
    """Gradients of <y, w> with respect to x, B, C and dt."""
    loss = lambda *xs: jnp.sum(fn(*xs, a, h0)[0] * w)
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))(*args)


@pytest.mark.parametrize("S,h0", [(512, False), (300, False), (512, True)],
                         ids=["two-chunks", "padded", "carried-state"])
def test_scan_past_the_overflow_is_finite_and_agrees(S, h0):
    args, a, state = _inputs(S, seed=S + h0, h0=h0)
    summed = -(args[3] * a)[:, :Q].sum(axis=1)          # (B, H), one chunk
    assert float(summed.min()) > OVERFLOW
    oracles = [_recurrence] + ([_reference] if S % Q == 0 and not h0 else [])
    y, h = _program(*args, a, state)
    w = jax.random.normal(jax.random.key(7), y.shape)
    grads = _grads(_program, args, a, state, w)
    for oracle in oracles:
        y_o, h_o = oracle(*args, a, state)
        _close(y, y_o)
        if h_o is not None:
            _close(h, h_o)
        for g, g_o in zip(grads, _grads(oracle, args, a, state, w)):
            _close(g, g_o)


def test_scan_resumes_from_its_final_state():
    """Prefill in two parts, the second from the first's final state, gives
    the one-pass outputs and final state."""
    args, a, _ = _inputs(2 * Q, seed=3, h0=False)
    y, h = _program(*args, a)
    y1, h1 = _program(*(t[:, :Q] for t in args), a)
    y2, h2 = _program(*(t[:, Q:] for t in args), a, h1)
    _close(jnp.concatenate([y1, y2], axis=1), y)
    _close(h2, h)


def test_hybrid_model_at_chunk_256_matches_one_step_chunks():
    """The hybrid family's smoke model at the published chunk, with every
    layer's summed decay past the overflow inside its first chunk: the loss
    and its gradients are finite, the logits match the same model with
    chunks of one step (a plain recurrence, no positive exponent anywhere),
    and prefill then one decode step match the full forward pass."""
    cfg = dataclasses.replace(smoke(get_config("zamba2_7b")), ssm_chunk=Q)
    mod = registry(cfg.family)
    params = init_params(jax.random.key(0), mod.model_defs(cfg))
    # dt = softplus(raw + 2) > 2 at every step, A = -1: a decay past 500
    # over one chunk
    params["mamba"]["ssm"]["dt_bias"] = jnp.full_like(
        params["mamba"]["ssm"]["dt_bias"], 2.0)
    S = 300
    toks = jax.random.randint(jax.random.key(1), (2, S + 1), 0, cfg.vocab)

    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: mod.loss_fn(cfg)(p, {"tokens": toks})[0]))(params)
    assert bool(jnp.isfinite(loss))
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in jax.tree.leaves(grads))

    logits_of = lambda c: jax.jit(lambda p, t: mod.forward(c, p, t)[0])(
        params, toks[:, :S])[..., :cfg.vocab]
    logits = logits_of(cfg)
    _close(logits, logits_of(dataclasses.replace(cfg, ssm_chunk=1)))

    last, cache, pos = mod.prefill(cfg, params, toks[:, :S - 1], S)
    _close(last[..., :cfg.vocab], logits[:, S - 2])
    step, _ = mod.decode_step(cfg, params, toks[:, S - 1:S], cache, pos)
    _close(step[..., :cfg.vocab], logits[:, S - 1])
