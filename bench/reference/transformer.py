"""Plain reference of a decoder-only GQA transformer LM with a vision prefix.

InternLM2 as the language model of InternVL2 (arXiv:2404.16821): RMSNorm
pre-norm blocks, grouped-query attention with rotary embeddings (rotate
half), SwiGLU feed-forward, untied embedding and head.  The vision
encoder's output arrives as ``vision_embeds`` and is prepended to the text
embeddings; the loss is next-token cross-entropy over the text positions.

The parameter layout (names, stacked layers, the vocabulary padded to a
multiple of 512) is the one the benchmark's weights are made in.  Rows and
columns past ``vocab`` are layout only: no token selects them and the loss
reads logits of the first ``vocab`` columns alone, so they get no gradient.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
RMS_EPS = 1e-6
Q_BLOCK = 512          # query rows per attention block (memory, not math)


def padded(vocab: int) -> int:
    return -(-vocab // 512) * 512


def param_specs(cfg: dict):
    """[(path, shape, dtype, init, scale)] of every parameter."""
    D, H, KV, Dh, F, L = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                          cfg["head_dim"], cfg["d_ff"], cfg["n_layers"])
    Vp, wt = padded(cfg["vocab"]), cfg["dtype"]
    specs = [("embed/tok", (Vp, D), wt, "embed", 0.02)]
    if not cfg["tie_embed"]:
        specs.append(("embed/unembed", (D, Vp), wt, "normal", 1.0))
    specs += [
        ("final_norm", (D,), "float32", "ones", 1.0),
        ("layers/attn/wk", (L, D, KV * Dh), wt, "normal", 1.0),
        ("layers/attn/wo", (L, H * Dh, D), wt, "normal", 1.0),
        ("layers/attn/wq", (L, D, H * Dh), wt, "normal", 1.0),
        ("layers/attn/wv", (L, D, KV * Dh), wt, "normal", 1.0),
        ("layers/mlp/w_gate", (L, D, F), wt, "normal", 1.0),
        ("layers/mlp/w_in", (L, D, F), wt, "normal", 1.0),
        ("layers/mlp/w_out", (L, F, D), wt, "normal", 1.0),
        ("layers/norm1", (L, D), "float32", "ones", 1.0),
        ("layers/norm2", (L, D), "float32", "ones", 1.0),
    ]
    return specs


def rms_norm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + RMS_EPS) \
        * scale


def rope(x, theta):
    """Rotate-half rotary embedding; x (B, S, heads, Dh)."""
    S, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def causal_attention(q, k, v, mm):
    """Softmax attention, each query attending to itself and earlier keys.
    q (B, S, H, Dh); k, v (B, S, H, Dh).  Computed in blocks of query rows
    so that the scores of one block are live at a time."""
    B, S, H, Dh = q.shape
    nb = S // Q_BLOCK if S % Q_BLOCK == 0 else 1
    blk = S // nb
    scale = 1.0 / math.sqrt(Dh)
    qb = jnp.moveaxis(q.reshape(B, nb, blk, H, Dh), 1, 0)

    @jax.checkpoint
    def one(args):
        qi, i = args
        s = mm("bqhd,bkhd->bhqk", qi, k) * scale
        rows = i * blk + jnp.arange(blk)[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= rows, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(one, (qb, jnp.arange(nb)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, Dh)


def loss_fn(cfg: dict):
    D, H, KV, Dh = (cfg["d_model"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["head_dim"])
    V, theta, n_vis = cfg["vocab"], cfg["rope_theta"], cfg["n_patches"]

    def fn(p, batch, tap, mm):
        tokens = batch["tokens"]
        x = p["embed/tok"][tokens[:, :-1]]
        if n_vis:
            x = jnp.concatenate([batch["vision_embeds"].astype(F32), x], 1)
        B, S, _ = x.shape
        layers = {k[len("layers/"):]: v for k, v in p.items()
                  if k.startswith("layers/")}

        @jax.checkpoint
        def block(x, lp, idx):
            h = rms_norm(x, lp["norm1"])
            q = mm("bsd,dh->bsh", h, lp["attn/wq"]).reshape(B, S, H, Dh)
            k = mm("bsd,dh->bsh", h, lp["attn/wk"]).reshape(B, S, KV, Dh)
            v = mm("bsd,dh->bsh", h, lp["attn/wv"]).reshape(B, S, KV, Dh)
            q, k = rope(q, theta), rope(k, theta)
            k, v = jnp.repeat(k, H // KV, 2), jnp.repeat(v, H // KV, 2)
            a = causal_attention(q, k, v, mm).reshape(B, S, H * Dh)
            x = x + mm("bsh,hd->bsd", a, lp["attn/wo"])
            h = rms_norm(x, lp["norm2"])
            g = mm("bsd,df->bsf", h, lp["mlp/w_gate"])
            u = mm("bsd,df->bsf", h, lp["mlp/w_in"])
            x = x + mm("bsf,fd->bsd", jax.nn.silu(g) * u, lp["mlp/w_out"])
            return tap(x, idx)

        def body(carry, xs):
            x, st = carry
            lp, idx = xs
            x, s = block(x, lp, idx)
            return (x, st + s), None

        (x, a_st), _ = jax.lax.scan(
            body, (x, jnp.zeros((4,), F32)),
            (layers, jnp.arange(cfg["n_layers"], dtype=jnp.uint32)))
        x = rms_norm(x, p["final_norm"])[:, n_vis:]
        w = (p["embed/unembed"][:, :V] if "embed/unembed" in p
             else p["embed/tok"][:V].T)
        return xent(x, w, tokens[:, 1:], mm), a_st

    return fn


def xent(x, w, labels, mm):
    """Mean next-token cross-entropy; one batch row's logits at a time."""
    @jax.checkpoint
    def row(args):
        xr, lr = args
        logits = mm("sd,dv->sv", xr, w)
        gold = jnp.take_along_axis(logits, lr[:, None], -1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

    return jnp.sum(jax.lax.map(row, (x, labels))) / labels.size
