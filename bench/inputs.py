"""Weights and training batches, made on the device from ``--seed``.

Both are pure functions of the seed, so the program and the reference start
from the same weights and see the same rows.

Weights: one value per parameter path, keyed by the path's CRC32, drawn by
the parameter's init rule (fan-in truncated normal for projections, a
0.02-scaled normal for embeddings, ones or zeros for norms and biases) and
cast to its storage type.

Batches: the affine-recurrence token pattern of the program's synthetic
stream (``tok[t+1] = (a * tok[t] + c) mod V`` with per-row ``a`` in [1, 8),
``c`` and ``tok[0]`` uniform, then 5% of positions replaced by uniform
noise), so the loss has something to learn.  The recurrence runs as an
associative scan of affine maps mod V, in uint32 (V**2 + V < 2**32 for
every vocabulary here).  Each row of each step has its own key.
"""

from __future__ import annotations

import math
import zlib
from typing import Dict

import jax
import jax.numpy as jnp

NOISE = 0.05
VISION_SCALE = 0.02     # the stubbed vision embeddings, like token embeddings


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative integer seed, also past 32 bits."""
    if seed < 0 or seed >= 1 << 64:
        raise ValueError(f"--seed {seed}: need 0 <= seed < 2**64")
    k = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, seed >> 32)


def _init(key, shape, dtype, init, scale):
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "embed":
        return (jax.random.normal(key, shape, jnp.float32)
                * scale).astype(dtype)
    if init == "normal":
        fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
        std = scale / math.sqrt(max(fan_in, 1))
        return (jax.random.truncated_normal(key, -2.0, 2.0, shape,
                                            jnp.float32) * std).astype(dtype)
    raise ValueError(f"unknown init {init!r}")


def weights(key: jax.Array, specs) -> Dict[str, jax.Array]:
    """{path: array} for ``specs`` = [(path, shape, dtype, init, scale)];
    trace it under one ``jax.jit`` to make every leaf on the device."""
    return {path: _init(jax.random.fold_in(key, zlib.crc32(path.encode())),
                        tuple(shape), jnp.dtype(dtype), init, scale)
            for path, shape, dtype, init, scale in specs}


def nest(flat: Dict[str, jax.Array]) -> dict:
    """{"a/b": x} -> {"a": {"b": x}}: the program's parameter tree."""
    out: dict = {}
    for path, v in flat.items():
        *parents, leaf = path.split("/")
        d = out
        for p in parents:
            d = d.setdefault(p, {})
        d[leaf] = v
    return out


def tokens(key: jax.Array, rows: int, length: int, vocab: int) -> jax.Array:
    """(rows, length) int32 token ids of the affine-recurrence pattern."""
    ka, kc, k0, kf, kn = jax.random.split(key, 5)
    V = jnp.uint32(vocab)
    a = jax.random.randint(ka, (rows, 1), 1, 8).astype(jnp.uint32)
    c = jax.random.randint(kc, (rows, 1), 0, vocab).astype(jnp.uint32)
    t0 = jax.random.randint(k0, (rows, 1), 0, vocab).astype(jnp.uint32)
    # position t applies the map x -> a x + c, t times, to t0
    A = jnp.broadcast_to(a, (rows, length - 1))
    C = jnp.broadcast_to(c, (rows, length - 1))

    def compose(f, g):           # g after f
        (a1, c1), (a2, c2) = f, g
        return (a2 * a1) % V, (a2 * c1 + c2) % V

    An, Cn = jax.lax.associative_scan(compose, (A, C), axis=1)
    rest = (An * t0 + Cn) % V
    toks = jnp.concatenate([t0, rest], axis=1).astype(jnp.int32)
    flip = jax.random.uniform(kf, (rows, length)) < NOISE
    noise = jax.random.randint(kn, (rows, length), 0, vocab, jnp.int32)
    return jnp.where(flip, noise, toks)


def batch(key: jax.Array, step, traffic: dict, cfg: dict) -> dict:
    """The global batch of training step ``step`` (0-based)."""
    k = jax.random.fold_in(key, step)
    rows, seq = traffic["global_batch"], traffic["seq"]
    n_vis = cfg.get("n_patches", 0)
    out = {"tokens": tokens(jax.random.fold_in(k, 0), rows,
                            seq - n_vis + 1, cfg["vocab"])}
    if n_vis:
        out["vision_embeds"] = (jax.random.normal(
            jax.random.fold_in(k, 1), (rows, n_vis, cfg["d_model"]),
            jnp.float32) * VISION_SCALE).astype(jnp.dtype(cfg["dtype"]))
    return out
