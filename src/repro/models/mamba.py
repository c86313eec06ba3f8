"""Attention-free Mamba2 LM (the ``ssm`` family; mamba2-1.3b).

Embed → L × [pre-norm residual SSD block] → final norm → unembed.
Decode state is O(1) per token, whatever the context length.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.fixed_point import QuantStats
from repro.models import ssm as ssm_lib
from repro.dist.sharding import logical_constraint
from repro.models.common import (ParamDef, embed_defs, embed_lookup,
                                 fused_unembed_xent, rms_norm, softmax_xent,
                                 unembed)
from repro.models.transformer import stack_defs, _dtype


def model_defs(cfg: ModelConfig) -> Dict[str, Any]:
    dt = _dtype(cfg)
    layer = {
        "norm": ParamDef((cfg.d_model,), (None,), init="ones", dtype=jnp.float32),
        "ssm": ssm_lib.ssm_defs(cfg, dt),
    }
    return {
        "embed": embed_defs(cfg.vocab, cfg.d_model, tie=cfg.tie_embed, dtype=dt),
        "layers": stack_defs(cfg.n_layers, layer),
        "final_norm": ParamDef((cfg.d_model,), (None,), init="ones",
                               dtype=jnp.float32),
    }


def cache_struct(cfg: ModelConfig, batch: int, max_seq: int):
    """SSM decode cache: (state, conv_tail) per layer — O(1) in seq_len."""
    L = cfg.n_layers
    H, P = ssm_lib.n_ssm_heads(cfg), cfg.ssm_head_dim
    cc = ssm_lib.conv_channels(cfg)
    return (
        jax.ShapeDtypeStruct((L, batch, H, P, cfg.ssm_state), jnp.float32),
        jax.ShapeDtypeStruct((L, batch, cfg.ssm_conv - 1, cc), jnp.float32),
    )


def cache_logical(cfg: ModelConfig):
    return (("layers", "batch", "heads", None, None),
            ("layers", "batch", None, "tp"))


def init_cache(cfg: ModelConfig, batch: int, max_seq: int = 0):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_struct(cfg, batch, max_seq))


def _run_stack(cfg, layers, x, *, mode, cache, qctx):
    def body(carry, xs):
        h, stats_acc = carry
        p, idx, layer_cache = xs
        out, new_cache = ssm_lib.ssm_apply(
            cfg, p["ssm"], rms_norm(h, p["norm"]), mode=mode,
            cache=layer_cache)
        h = h + out
        stats = QuantStats.zero()
        if qctx is not None:
            h, stats = qctx.tap(h, idx)
            stats = stats if stats is not None else QuantStats.zero()
        # sequence-parallel carry: the layer-scan residual is the backward
        # pass's dominant saved tensor; sharding it on the model axis divides
        # that footprint by the TP degree (SSM internals re-gather as needed)
        h = logical_constraint(h, "batch", "tp_seq", "embed")
        return (h, stats_acc.merge(stats)), new_cache

    if cfg.remat == "full":
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    elif cfg.remat == "dots":
        body = jax.checkpoint(body,
                              policy=jax.checkpoint_policies.checkpoint_dots)

    idxs = jnp.arange(cfg.n_layers, dtype=jnp.uint32)
    (x, stats), new_cache = jax.lax.scan(body, (x, QuantStats.zero()),
                                         (layers, idxs, cache),
                                         unroll=cfg.probe_unroll)
    if mode == "train":
        new_cache = None
    return x, new_cache, stats


def forward(cfg: ModelConfig, params, tokens, *, qctx=None, mode="train",
            cache=None, cache_pos=None, vision_embeds=None,
            hidden_only=False):
    x = embed_lookup(params["embed"]["tok"], tokens, seq_axis=None).astype(_dtype(cfg))
    B = x.shape[0]
    if cache is None:
        cache = init_cache(cfg, B)
    x, new_cache, stats = _run_stack(cfg, params["layers"], x, mode=mode,
                                     cache=cache, qctx=qctx)
    x = rms_norm(x, params["final_norm"])
    if hidden_only:
        return x, new_cache, jnp.zeros((), jnp.float32), stats
    if mode == "prefill":
        x = x[:, -1:]
    logits = unembed(x, params["embed"], cfg.vocab)
    return logits, new_cache, jnp.zeros((), jnp.float32), stats


def loss_fn(cfg: ModelConfig):
    def fn(params, batch, qctx=None):
        tokens = batch["tokens"]
        hidden, _, _, stats = forward(cfg, params, tokens[:, :-1], qctx=qctx,
                                      hidden_only=True)
        loss = fused_unembed_xent(hidden, params["embed"], cfg.vocab,
                                  tokens[:, 1:], batch.get("loss_mask"),
                                  unroll=cfg.probe_unroll)
        return loss, {"act_stats": stats}
    return fn


def prefill(cfg: ModelConfig, params, tokens, max_seq: int, *, qctx=None,
            vision_embeds=None):
    logits, cache, _, _ = forward(cfg, params, tokens, qctx=qctx,
                                  mode="prefill")
    B = tokens.shape[0]
    pos = jnp.full((B,), tokens.shape[1], jnp.int32)
    return logits[:, -1], cache, pos


def decode_step(cfg: ModelConfig, params, tokens, cache, pos, qctx=None):
    logits, new_cache, _, _ = forward(cfg, params, tokens, qctx=qctx,
                                      mode="decode", cache=cache,
                                      cache_pos=pos)
    return logits[:, -1], new_cache


def count_params(cfg: ModelConfig) -> float:
    per_layer = cfg.d_model + ssm_lib.count_ssm_params(cfg)
    total = cfg.n_layers * per_layer + cfg.d_model
    total += cfg.vocab * cfg.d_model * (1 if cfg.tie_embed else 2)
    return float(total)
