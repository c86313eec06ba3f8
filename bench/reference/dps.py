"""Plain reference of dynamic fixed-point (DPS) training, after the paper.

Everything the training configuration states, written out in float32
``jax.numpy``: stochastic rounding onto the ⟨IL, FL⟩ grid (the paper's
Eq. 2), the per-domain controller (Alg. 2: IL follows the overflow rate,
FL the mean relative rounding error), the activation tap that quantizes a
layer's output on the way forward and its cotangent on the way back, and
one AdamW step.  Matmul weights are held in the storage type the
configuration states (bfloat16) and every arithmetic operation runs in
float32 at the highest matmul precision.

This module imports nothing of the program.  Its random bits come from its
own keys, so it agrees with the program in distribution and not element by
element: the numbers compared are losses and per-leaf norms over millions
of elements, which stochastic rounding moves by far less than the limits.
"""

from __future__ import annotations

import re
from functools import partial
from typing import Callable, Dict, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

# Grid integers stay exact in float32 while IL - 1 + FL <= 24.
EXACT_SPAN = 24


class Fmt(NamedTuple):
    il: int
    fl: int


class Hyper(NamedTuple):
    """One domain's controller settings (the configuration's values)."""
    il_init: int
    fl_init: int
    r_max: float = 1e-4
    e_max: float = 1e-4
    il_min: int = 2
    il_max: int = 16
    fl_min: int = 0
    fl_max: int = 23
    max_total: int = 32


def pow2(n: jax.Array) -> jax.Array:
    """Exact float32 2**n for integer n in [-126, 127], from exponent bits."""
    n = jnp.clip(jnp.asarray(n, jnp.int32), -126, 127)
    return jax.lax.bitcast_convert_type((n + 127) << 23, F32)


def zero_stats() -> jax.Array:
    """[count, nonzero, overflow, sum of relative error over nonzero]."""
    return jnp.zeros((4,), F32)


def quantize(x: jax.Array, fmt: jax.Array, key: jax.Array):
    """Stochastic rounding of ``x`` onto the grid ``fmt = [IL, FL]``.

    Returns the grid value in ``x``'s dtype and the event's statistics.
    """
    il, fl = fmt[0], fmt[1]
    xf = x.astype(F32)
    y = xf * pow2(fl)
    hi = pow2(il - 1 + fl)
    over = (y > hi - 1.0) | (y < -hi)
    yc = jnp.clip(y, -hi, hi - 1.0)
    u = jax.random.uniform(key, x.shape, F32)
    k = jnp.clip(jnp.floor(yc + u), -hi, hi - 1.0)
    err = jnp.abs(k - yc)
    nz = jnp.abs(yc) > 0
    rel = jnp.where(nz, err / jnp.where(nz, jnp.abs(yc), 1.0), 0.0)
    stats = jnp.stack([jnp.float32(x.size), jnp.sum(nz.astype(F32)),
                       jnp.sum(over.astype(F32)), jnp.sum(rel)])
    return (k * pow2(-fl)).astype(x.dtype), stats


def controller_update(fmt: Fmt, stats: np.ndarray, h: Hyper) -> Fmt:
    """Alg. 2: IL +1 when the overflow rate exceeds R_max, else -1; FL +1
    when the mean relative error exceeds E_max, else -1.  Clamped to the
    domain's range, to the float32-exact span and to the width cap."""
    count, nonzero, overflow, rel = (float(v) for v in stats)
    r = overflow / max(count, 1.0)
    e = rel / max(nonzero, 1.0)
    il = fmt.il + (1 if r > h.r_max else -1)
    fl = fmt.fl + (1 if e > h.e_max else -1)
    il = min(max(il, h.il_min), h.il_max)
    fl = min(max(fl, h.fl_min), h.fl_max)
    fl = min(fl, EXACT_SPAN + 1 - il, h.max_total - il)
    return Fmt(il, fl)


# ---------------------------------------------------------------------------
# Activation tap: quantize forward with the activations' format and the
# cotangent backward with the gradients' format.
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _tap(x, fmt_a, fmt_g, kf, kb):
    return quantize(x, fmt_a, kf)


def _tap_fwd(x, fmt_a, fmt_g, kf, kb):
    return quantize(x, fmt_a, kf), (fmt_g, kb)


def _tap_bwd(res, cot):
    fmt_g, kb = res
    g, _ = quantize(cot[0], fmt_g, kb)
    return g, None, None, None, None


_tap.defvjp(_tap_fwd, _tap_bwd)


class Tap(NamedTuple):
    fmt_a: jax.Array
    fmt_g: jax.Array
    key: jax.Array

    def __call__(self, x, layer):
        kf = jax.random.fold_in(self.key, layer)
        kb = jax.random.fold_in(kf, 1)
        return _tap(x, self.fmt_a, self.fmt_g, kf, kb)


def no_tap(x, layer):
    """The tap of a configuration that runs without DPS."""
    return x, zero_stats()


# ---------------------------------------------------------------------------
# Matmuls: float32 at the highest precision, or the control's float8.
# ---------------------------------------------------------------------------

def _einsum(eq, a, b):
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def make_mm(precision: str) -> Callable:
    """``mm(eq, a, b)``: an einsum in float32 at the highest precision, or
    with ``"fp8"`` both operands rounded to float8 e4m3 first (on the way
    back the gradient passes through the same rounding): the control, one
    precision step below the bfloat16 that the configuration states."""
    if precision == "f32":
        return lambda eq, a, b: _einsum(eq, a.astype(F32), b.astype(F32))
    if precision == "fp8":
        f8 = lambda t: t.astype(jnp.float8_e4m3fn).astype(F32)
        return lambda eq, a, b: _einsum(eq, f8(a), f8(b))
    raise ValueError(f"unknown precision {precision!r}")


# ---------------------------------------------------------------------------
# Parameters: path strings, the quantization carve-outs, the weights.
# ---------------------------------------------------------------------------

def quantized(path: str, exclude: Sequence[str]) -> bool:
    """The configuration's policy: every leaf is quantized except those
    whose path matches one of its carve-out patterns."""
    return not any(re.search(p, path) for p in exclude)


def quantize_tree(tree: Dict[str, jax.Array], fmt, key, exclude):
    out, stats = {}, zero_stats()
    for i, (path, leaf) in enumerate(sorted(tree.items())):
        if quantized(path, exclude):
            out[path], s = quantize(leaf, fmt, jax.random.fold_in(key, i))
            stats = stats + s
        else:
            out[path] = leaf
    return out, stats


# ---------------------------------------------------------------------------
# AdamW (decoupled weight decay, bias-corrected, global-norm clipping).
# ---------------------------------------------------------------------------

def learning_rate(opt: dict, count) -> jax.Array:
    s = jnp.asarray(count, F32)
    warm, total = opt["warmup"], opt["total_steps"]
    prog = jnp.clip((s - warm) / max(total - warm, 1), 0.0, 1.0)
    floor = opt["lr_floor"]
    cos = floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return opt["lr"] * jnp.where(s < warm, s / max(warm, 1), cos)


def adamw(opt: dict, params, grads, m, v, count):
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(F32)))
                         for g in grads.values()))
    clip = (jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(gnorm, 1e-9))
            if opt["clip_norm"] else 1.0)
    lr = learning_rate(opt, count)
    t = jnp.asarray(count, F32) + 1.0
    bc1, bc2 = 1.0 - opt["b1"] ** t, 1.0 - opt["b2"] ** t
    new_p, new_m, new_v = {}, {}, {}
    for k, p in params.items():
        g = grads[k].astype(F32) * clip
        new_m[k] = opt["b1"] * m[k] + (1 - opt["b1"]) * g
        new_v[k] = opt["b2"] * v[k] + (1 - opt["b2"]) * g * g
        step = (new_m[k] / bc1 / (jnp.sqrt(new_v[k] / bc2) + opt["eps"])
                + opt["weight_decay"] * p.astype(F32))
        upd = (-lr * step).astype(p.dtype)
        new_p[k] = (p.astype(F32) + upd.astype(F32)).astype(p.dtype)
    return new_p, new_m, new_v


# ---------------------------------------------------------------------------
# One training step of the configuration, and three of them from the seed.
# ---------------------------------------------------------------------------

class Domains(NamedTuple):
    weights: Fmt
    acts: Fmt
    grads: Fmt


def make_step(loss_fn: Callable, opt: dict, exclude: Sequence[str],
              precision: str, enabled: bool = True):
    """A reference step: ``step(params, m, v, batch, fmts, count, key)``.

    ``loss_fn(params, batch, tap, mm) -> (loss, act_stats)`` is the model's
    plain forward pass and loss.  The gradient is the mean of one gradient
    per batch row, taken one row after another, each row with its own
    activation-tap stream: for rows of equal length that is the gradient of
    the mean loss, and where data-parallel replicas own equal shares of the
    rows it is the plain mean over the replicas, with no wire.  The
    gradient and the update are two programs, so that their temporaries are
    never live together.  ``enabled=False`` is the same training with no
    quantization anywhere.
    """
    if not enabled:
        exclude = (".",)           # every path matches: nothing quantized
    mm = make_mm(precision)

    @jax.jit
    def grads(params, batch, fmts, key):
        fw, fa, fg = fmts[0], fmts[1], fmts[2]
        k_w, k_a = jax.random.split(key, 2)
        qparams, w_st = quantize_tree(params, fw, k_w, exclude)
        rows = jax.tree.map(lambda x: x[:, None], batch)   # one row each
        n = batch["tokens"].shape[0]

        def f(p32):
            @jax.checkpoint
            def one(xs):
                row, r = xs
                tap = (Tap(fa, fg, jax.random.fold_in(k_a, r)) if enabled
                       else no_tap)
                return loss_fn(p32, row, tap, mm)

            losses, a_st = jax.lax.map(one, (rows, jnp.arange(
                n, dtype=jnp.uint32)))
            return jnp.mean(losses), jnp.sum(a_st, 0)

        p32 = {k: v.astype(F32) for k, v in qparams.items()}
        (loss, a_st), g = jax.value_and_grad(f, has_aux=True)(p32)
        return loss, g, w_st, a_st

    @partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(params, m, v, g, fmts, count, key):
        fw, fg = fmts[0], fmts[2]
        k_g, k_w2 = jax.random.split(key, 2)
        g, g_st = quantize_tree(g, fg, k_g, exclude)
        new_p, new_m, new_v = adamw(opt, params, g, m, v, count)
        new_p, w_st2 = quantize_tree(new_p, fw, k_w2, exclude)
        return new_p, new_m, new_v, w_st2, g_st

    def step(params, m, v, batch, fmts, count, key):
        k1, k2 = jax.random.split(key)
        loss, g, w_st, a_st = grads(params, batch, fmts, k1)
        params, m, v, w_st2, g_st = update(params, m, v, g, fmts, count, k2)
        return loss, params, m, v, jnp.stack([w_st + w_st2, a_st, g_st])

    return step


def leaf_norms(tree: Dict[str, jax.Array]) -> Dict[str, jax.Array]:
    return {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(F32))))
            for k, v in tree.items()}


def run_three_steps(step, params, batches, hypers: Domains, opt: dict,
                    key) -> dict:
    """Drive ``step`` from ``params`` (donated) through three steps.

    Returns each step's loss, the per-leaf norms of the first gradient as
    AdamW received it (its first moment after one step, divided by
    1 - b1), and the parameters after three steps."""
    m = {k: jnp.zeros(p.shape, F32) for k, p in params.items()}
    v = {k: jnp.zeros(p.shape, F32) for k, p in params.items()}
    fmts = Domains(*(Fmt(h.il_init, h.fl_init) for h in hypers))
    losses, grad_norms = [], None
    for i, batch in enumerate(batches):
        loss, params, m, v, stats = step(
            params, m, v, batch, jnp.asarray([list(f) for f in fmts],
                                             jnp.int32),
            i, jax.random.fold_in(key, i))
        losses.append(float(loss))
        if i == 0:
            grad_norms = {k: float(n) / (1 - opt["b1"])
                          for k, n in leaf_norms(m).items()}
        stats = np.asarray(stats)
        fmts = Domains(*(controller_update(f, s, h)
                         for f, s, h in zip(fmts, stats, hypers)))
    return {"losses": losses, "grad_norms": grad_norms, "params": params}
