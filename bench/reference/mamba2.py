"""Plain reference of the Mamba2 language model (arXiv:2405.21060).

Each layer: RMSNorm, one input projection to [z, x, B, C, dt], a depthwise
causal convolution of width ``ssm_conv`` with SiLU over [x, B, C],
dt = softplus(dt + dt_bias), A = -exp(A_log), the SSD recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T,    y_t = C_t h_t + D x_t,

gated RMSNorm of y * silu(z), output projection, residual.  The head is
tied to the embedding.  The recurrence is evaluated in the paper's chunked
"minimal SSD" form (its Listing 1): a masked quadratic form inside each
chunk of ``ssm_chunk`` steps and a recurrence over chunk states, which is
the same sum as the sequential scan.

The convolution's weight layout is (width, channels) with the last row
applied to the current position.  Rows of the embedding past ``vocab`` are
layout only (see ``transformer.py``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import transformer as tfm

F32 = jnp.float32


def dims(cfg: dict):
    di = cfg["ssm_expand"] * cfg["d_model"]
    return di, di // cfg["ssm_head_dim"], cfg["ssm_state"]


def param_specs(cfg: dict):
    D, L, K, wt = cfg["d_model"], cfg["n_layers"], cfg["ssm_conv"], cfg["dtype"]
    di, H, N = dims(cfg)
    cc = di + 2 * N
    return [
        ("embed/tok", (tfm.padded(cfg["vocab"]), D), wt, "embed", 0.02),
        ("final_norm", (D,), "float32", "ones", 1.0),
        ("layers/norm", (L, D), "float32", "ones", 1.0),
        ("layers/ssm/a_log", (L, H), "float32", "zeros", 1.0),
        ("layers/ssm/conv_b", (L, cc), wt, "zeros", 1.0),
        ("layers/ssm/conv_w", (L, K, cc), wt, "normal", 1.0),
        ("layers/ssm/d_skip", (L, H), "float32", "ones", 1.0),
        ("layers/ssm/dt_bias", (L, H), "float32", "zeros", 1.0),
        ("layers/ssm/norm_scale", (L, di), "float32", "ones", 1.0),
        ("layers/ssm/w_in", (L, D, 2 * di + 2 * N + H), wt, "normal", 1.0),
        ("layers/ssm/w_out", (L, di, D), wt, "normal", 1.0),
    ]


def segsum(a):
    """a (..., T) -> (..., T, T) with [i, j] = sum of a[j+1 .. i] for j <= i,
    and -inf above the diagonal (the paper's stable segment sum)."""
    T = a.shape[-1]
    x = jnp.repeat(a[..., None], T, axis=-1)            # [.., i, j] = a_i
    x = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), x, 0.0)
    s = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool), 0), s, -jnp.inf)


def ssd(x, dt, A, Bm, Cm, chunk, mm):
    """x (b, S, h, p); dt (b, S, h); A (h,); Bm, Cm (b, S, n) -> y."""
    b, S, h, p = x.shape
    n = Bm.shape[-1]
    Q = min(chunk, S)
    c = S // Q
    X = (x * dt[..., None]).reshape(b, c, Q, h, p)
    Ad = jnp.moveaxis((dt * A).reshape(b, c, Q, h), -1, 1)      # b h c l
    Bc, Cc = Bm.reshape(b, c, Q, n), Cm.reshape(b, c, Q, n)
    Acum = jnp.cumsum(Ad, -1)
    # inside each chunk
    Lmat = jnp.exp(segsum(Ad))                                  # b h c l s
    CB = mm("bcln,bcsn->bcls", Cc, Bc)
    W = CB[:, None] * Lmat                                      # b h c l s
    Y_diag = mm("bhcls,bcshp->bclhp", W, X)
    # each chunk's final state
    decay = jnp.exp(Acum[..., -1:] - Acum)                      # b h c l
    states = mm("bcln,bclhp->bchpn", Bc,
                X * jnp.moveaxis(decay, 1, -1)[..., None])
    # recurrence over chunks, from a zero initial state
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    chunk_decay = jnp.exp(segsum(jnp.pad(Acum[..., -1], ((0, 0), (0, 0),
                                                         (1, 0)))))
    new_states = jnp.einsum("bhzc,bchpn->bzhpn", chunk_decay, states,
                            precision=jax.lax.Precision.HIGHEST)
    states = new_states[:, :-1]
    # the states' contribution to each position
    Y_off = mm("bcln,bchpn->bclhp", Cc, states) \
        * jnp.moveaxis(jnp.exp(Acum), 1, -1)[..., None]
    return (Y_diag + Y_off).reshape(b, S, h, p)


def loss_fn(cfg: dict):
    D, K, chunk = cfg["d_model"], cfg["ssm_conv"], cfg["ssm_chunk"]
    di, H, N = dims(cfg)
    P, V = cfg["ssm_head_dim"], cfg["vocab"]

    def fn(p, batch, tap, mm):
        tokens = batch["tokens"]
        x = p["embed/tok"][tokens[:, :-1]]
        B, S, _ = x.shape
        layers = {k[len("layers/"):]: v for k, v in p.items()
                  if k.startswith("layers/")}

        @jax.checkpoint
        def block(x, lp, idx):
            h = tfm.rms_norm(x, lp["norm"])
            zxbcdt = mm("bsd,dk->bsk", h, lp["ssm/w_in"])
            z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * N],
                          zxbcdt[..., 2 * di + 2 * N:])
            w = lp["ssm/conv_w"]
            xp = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
            conv = sum(xp[:, j:j + S] * w[j] for j in range(K))
            xbc = jax.nn.silu(conv + lp["ssm/conv_b"])
            xs = xbc[..., :di].reshape(B, S, H, P)
            Bm, Cm = xbc[..., di:di + N], xbc[..., di + N:]
            dt = jax.nn.softplus(dt + lp["ssm/dt_bias"])
            A = -jnp.exp(lp["ssm/a_log"])
            y = ssd(xs, dt, A, Bm, Cm, chunk, mm)
            y = (y + lp["ssm/d_skip"][None, None, :, None] * xs)
            y = tfm.rms_norm(y.reshape(B, S, di) * jax.nn.silu(z),
                             lp["ssm/norm_scale"])
            return tap(x + mm("bsk,kd->bsd", y, lp["ssm/w_out"]), idx)

        def body(carry, xs):
            x, st = carry
            lp, idx = xs
            x, s = block(x, lp, idx)
            return (x, st + s), None

        (x, a_st), _ = jax.lax.scan(
            body, (x, jnp.zeros((4,), F32)),
            (layers, jnp.arange(cfg["n_layers"], dtype=jnp.uint32)))
        x = tfm.rms_norm(x, p["final_norm"])
        return tfm.xent(x, p["embed/tok"][:V].T, tokens[:, 1:], mm), a_st

    return fn
