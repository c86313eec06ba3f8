"""Optimizers: SGD+momentum (the paper's recipe) and AdamW.

Interface (used by ``qtrain.make_train_step``):

    opt = make_optimizer(cfg)
    state = opt.init(params)
    updates, state = opt.update(grads, state, params, count=step)

Beyond-paper: optimizer state can be held in bf16 with **stochastic
rounding** on the state update (``state_dtype="bfloat16"``).  This is the
paper's own Gupta-et-al. insight applied to the optimizer — tiny moment
updates survive in expectation — and halves optimizer HBM, which is what
lets the 340B config fit a single 256-chip pod (see DESIGN §5).

Every ``update`` and ``update_shard`` runs under the named scope ``optim``,
so a profile of the compiled step can attribute the optimizer's device time.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp


def inv_decay(lr0: float, gamma: float, power: float):
    """The paper's schedule: lr = lr0 · (1 + γ·iter)^-pow (§4)."""
    def f(step):
        return lr0 * (1.0 + gamma * step.astype(jnp.float32)) ** (-power)
    return f


def cosine_schedule(lr0: float, warmup: int, total: int, floor: float = 0.1):
    def f(step):
        s = step.astype(jnp.float32)
        warm = s / max(warmup, 1)
        prog = jnp.clip((s - warmup) / max(total - warmup, 1), 0.0, 1.0)
        cos = floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
        return lr0 * jnp.where(s < warmup, warm, cos)
    return f


def _sr_cast(x: jax.Array, dtype, key) -> jax.Array:
    """Stochastically-rounded downcast (unbiased, Gupta et al.)."""
    if x.dtype == dtype or dtype == jnp.float32:
        return x.astype(dtype)
    # bf16: round fp32 mantissa bits 0..15 stochastically
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    noise = jax.random.bits(key, shape=x.shape, dtype=jnp.uint32) & 0xFFFF
    rounded = (bits + noise) & jnp.uint32(0xFFFF0000)
    return jax.lax.bitcast_convert_type(rounded, jnp.float32).astype(dtype)


def _layered(one, g, *rest, key):
    """Apply the per-leaf update ``one(g, *rest, key) -> tuple`` with bounded
    temporaries: layer-stacked leaves (ndim ≥ 3, unsharded leading dim) run
    under ``lax.map`` over the layer axis so the fp32 working copies are one
    layer-slice instead of one full stack each (at 100B+ scale those
    co-scheduled full-stack temporaries dominate step memory)."""
    if g.ndim >= 3 and g.shape[0] > 1 and g.size > (1 << 22):
        keys = jax.random.split(key, g.shape[0])
        return jax.lax.map(lambda xs: one(*xs), (g, *rest, keys))
    return one(g, *rest, key)


def _global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)))


def _clip_by_norm(tree, max_norm: float):
    n = _global_norm(tree)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(n, 1e-9))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), tree), n


def _clip_by_norm_shard(g: jax.Array, max_norm: float, axis_name):
    """Shard-local clip against the CROSS-SHARD global norm.

    A ZeRO rank holds one flat slice of the gradient, so the norm that the
    replicated :func:`_clip_by_norm` computes over the whole tree is
    recovered by psum-ing per-shard sums of squares over the data axis
    (zero padding contributes nothing).  ``axis_name=None`` (single shard)
    degrades to the local norm.
    """
    sq = jnp.sum(jnp.square(g.astype(jnp.float32)))
    if axis_name is not None:
        sq = jax.lax.psum(sq, axis_name)
    n = jnp.sqrt(sq)
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(n, 1e-9))
    return (g * scale).astype(g.dtype), n


def _shard_key(base: int, count, axis_name):
    """Per-step (and per-rank, under ZeRO) RNG for the stochastic state cast.

    The sharded path folds in ``axis_index`` so bf16 state updates draw
    distinct bits per rank; with fp32 state (``_sr_cast`` is the identity)
    the replicated and sharded paths are bit-identical regardless.
    """
    key = jax.random.fold_in(jax.random.key(base), count)
    if axis_name is not None:
        key = jax.random.fold_in(key, jax.lax.axis_index(axis_name))
    return key


@dataclasses.dataclass(frozen=True)
class SGDConfig:
    lr: float = 0.01
    momentum: float = 0.9
    weight_decay: float = 5e-4
    schedule: str = "inv"          # inv | const
    gamma: float = 1e-4            # paper: 0.0001
    power: float = 0.75            # paper: 0.75
    clip_norm: float = 0.0
    state_dtype: str = "float32"   # float32 | bfloat16 (stochastic-rounded)


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    warmup: int = 100
    total_steps: int = 10_000
    clip_norm: float = 1.0
    state_dtype: str = "float32"


class SGD:
    # precision domain whose ⟨IL, FL⟩ quantizes this optimizer's input
    # gradients (Alg. 1 line 17); the train step looks the format up in its
    # PrecisionPlan registry, so an optimizer wanting a dedicated
    # optimizer-input domain only has to name one here.
    grad_domain = "grads"

    def __init__(self, cfg: SGDConfig):
        self.cfg = cfg
        self.sched = (inv_decay(cfg.lr, cfg.gamma, cfg.power)
                      if cfg.schedule == "inv" else lambda s: cfg.lr)

    def init(self, params):
        dt = jnp.bfloat16 if self.cfg.state_dtype == "bfloat16" else jnp.float32
        return {"mu": jax.tree.map(lambda p: jnp.zeros(p.shape, dt), params)}

    def _state_dtype(self):
        return (jnp.bfloat16 if self.cfg.state_dtype == "bfloat16"
                else jnp.float32)

    def _leaf(self, lr, dt, g, mu, p, k):
        # Shared by update (per-leaf) and update_shard (flat ZeRO slice).
        # Whether LLVM contracts a product-feeding-an-add into an FMA
        # depends on the fused kernel's codegen, i.e. on tensor layout —
        # so the two layouts agree bit-exactly exactly when the scalar
        # products (wd·p, momentum·mu, lr·mu) are exact in f32, e.g. for
        # power-of-two lr/momentum/weight_decay; otherwise they may drift
        # by 1 ULP per step (measured on the CPU backend; no HLO-level
        # construct prevents the contraction).
        cfg = self.cfg
        gf = g.astype(jnp.float32) + cfg.weight_decay * p.astype(jnp.float32)
        mu_new = cfg.momentum * mu.astype(jnp.float32) + gf
        return (-lr * mu_new).astype(p.dtype), _sr_cast(mu_new, dt, k)

    def update(self, grads, state, params, count):
        with jax.named_scope("optim"):
            cfg = self.cfg
            if cfg.clip_norm:
                grads, _ = _clip_by_norm(grads, cfg.clip_norm)
            lr = self.sched(count)
            dt = self._state_dtype()
            key = jax.random.fold_in(jax.random.key(17), count)
            leaves, treedef = jax.tree_util.tree_flatten(state["mu"])
            keys = jax.random.split(key, len(leaves))
            keys = jax.tree_util.tree_unflatten(treedef, list(keys))

            one = lambda g, mu, p, k: self._leaf(lr, dt, g, mu, p, k)
            out = jax.tree.map(one, grads, state["mu"], params, keys)
            updates = jax.tree.map(lambda t: t[0], out,
                                   is_leaf=lambda x: isinstance(x, tuple))
            mu = jax.tree.map(lambda t: t[1], out,
                              is_leaf=lambda x: isinstance(x, tuple))
            return updates, {"mu": mu}

    # --- ZeRO-1 shard-local interface (see repro.dist.sharding) ---

    def init_shard(self, flat: jax.Array):
        """State for one flat slice (or the whole padded flat vector) of the
        flat ZeRO layout — :class:`~repro.dist.sharding.ZeroPartitioner` or
        the group-aligned :class:`~repro.dist.sharding.GroupAlignedPartitioner`
        (the math is layout-agnostic: padding slots carry zero gradients, so
        their state stays zero)."""
        return {"mu": jnp.zeros(flat.shape, self._state_dtype())}

    def update_shard(self, grads, state, params, count, axis_name=None):
        """One optimizer step on this rank's flat parameter slice.

        Works unchanged over either flat layout (plain or group-aligned —
        the slice is just a 1-D fp32 vector either way).
        Identical element-wise math to :meth:`update` (same ``_leaf``), so
        with fp32 state — and ``clip_norm`` off — the concatenation of
        per-shard updates is bit-exact with the replicated step.
        ``clip_norm`` uses the cross-shard global norm (psum over
        ``axis_name``), which sums squares in a different order than the
        per-leaf :func:`_global_norm`, so the clip scale (and hence the
        update) may differ from the replicated step in the last ULP.
        """
        with jax.named_scope("optim"):
            cfg = self.cfg
            if cfg.clip_norm:
                grads, _ = _clip_by_norm_shard(grads, cfg.clip_norm,
                                               axis_name)
            upd, mu = self._leaf(self.sched(count), self._state_dtype(),
                                 grads, state["mu"], params,
                                 _shard_key(17, count, axis_name))
            return upd, {"mu": mu}


class AdamW:
    grad_domain = "grads"   # see SGD.grad_domain

    def __init__(self, cfg: AdamWConfig):
        self.cfg = cfg
        self.sched = cosine_schedule(cfg.lr, cfg.warmup, cfg.total_steps)

    def init(self, params):
        dt = jnp.bfloat16 if self.cfg.state_dtype == "bfloat16" else jnp.float32
        z = lambda p: jnp.zeros(p.shape, dt)
        return {"m": jax.tree.map(z, params), "v": jax.tree.map(z, params)}

    def _state_dtype(self):
        return (jnp.bfloat16 if self.cfg.state_dtype == "bfloat16"
                else jnp.float32)

    def _bias_corrections(self, count):
        cfg = self.cfg
        t = count.astype(jnp.float32) + 1.0
        return 1.0 - cfg.b1 ** t, 1.0 - cfg.b2 ** t

    def _leaf(self, lr, bc1, bc2, dt, g, m, v, p, k):
        # shared by update (per-leaf) and update_shard (flat ZeRO slice);
        # see SGD._leaf for the FMA-contraction caveat on cross-layout
        # bit-exactness.
        cfg = self.cfg
        gf = g.astype(jnp.float32)
        m_new = cfg.b1 * m.astype(jnp.float32) + (1 - cfg.b1) * gf
        v_new = cfg.b2 * v.astype(jnp.float32) + (1 - cfg.b2) * gf * gf
        step = m_new / bc1 / (jnp.sqrt(v_new / bc2) + cfg.eps)
        step = step + cfg.weight_decay * p.astype(jnp.float32)
        k1, k2 = jax.random.split(k)
        return ((-lr * step).astype(p.dtype),
                _sr_cast(m_new, dt, k1), _sr_cast(v_new, dt, k2))

    def update(self, grads, state, params, count):
        with jax.named_scope("optim"):
            cfg = self.cfg
            if cfg.clip_norm:
                grads, _ = _clip_by_norm(grads, cfg.clip_norm)
            lr = self.sched(count)
            bc1, bc2 = self._bias_corrections(count)
            dt = self._state_dtype()
            key = jax.random.fold_in(jax.random.key(23), count)
            leaves, treedef = jax.tree_util.tree_flatten(state["m"])
            keys = jax.random.split(key, len(leaves))
            keys = jax.tree_util.tree_unflatten(treedef, list(keys))

            one = lambda g, m, v, p, k: self._leaf(lr, bc1, bc2, dt, g, m, v,
                                                   p, k)
            out = jax.tree.map(one, grads, state["m"], state["v"], params,
                               keys)
            pick = lambda i: jax.tree.map(
                lambda t: t[i], out, is_leaf=lambda x: isinstance(x, tuple))
            return pick(0), {"m": pick(1), "v": pick(2)}

    # --- ZeRO-1 shard-local interface (see repro.dist.sharding) ---

    def init_shard(self, flat: jax.Array):
        """State for one flat slice (or the whole padded flat vector) of the
        flat ZeRO layout (:class:`~repro.dist.sharding.ZeroPartitioner` or
        :class:`~repro.dist.sharding.GroupAlignedPartitioner`).

        ``m`` and ``v`` are distinct buffers on purpose: aliased leaves
        crash buffer donation ("Attempt to donate the same buffer twice")
        under ``jit(..., donate_argnums=...)`` without a resharding copy.
        """
        dt = self._state_dtype()
        return {"m": jnp.zeros(flat.shape, dt), "v": jnp.zeros(flat.shape, dt)}

    def update_shard(self, grads, state, params, count, axis_name=None):
        """One optimizer step on this rank's flat parameter slice.

        Same element-wise math as :meth:`update`; ``clip_norm`` uses the
        cross-shard global norm (psum over ``axis_name``).
        """
        with jax.named_scope("optim"):
            cfg = self.cfg
            if cfg.clip_norm:
                grads, _ = _clip_by_norm_shard(grads, cfg.clip_norm,
                                               axis_name)
            bc1, bc2 = self._bias_corrections(count)
            upd, m, v = self._leaf(self.sched(count), bc1, bc2,
                                   self._state_dtype(), grads, state["m"],
                                   state["v"], params,
                                   _shard_key(23, count, axis_name))
            return upd, {"m": m, "v": v}


def make_optimizer(cfg):
    if isinstance(cfg, SGDConfig):
        return SGD(cfg)
    if isinstance(cfg, AdamWConfig):
        return AdamW(cfg)
    raise TypeError(f"unknown optimizer config {type(cfg)}")
