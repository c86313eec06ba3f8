"""Operations and bytes, counted from shapes.

- ``param_count``: parameters of a configuration, with the published
  (unpadded) vocabulary.
- ``model_flops_per_step``: the matmul operations one training step needs:
  forward and backward (3x the forward) over every position, the head over
  the positions that carry a loss, causal attention at half the square, and
  for SSD layers the chunked dual form's terms with the intra-chunk
  square at half.  Recomputation and padding do not count.
- ``jaxpr_matmul_flops``: the matmul operations a traced program executes,
  recomputation and padding included, summed over the ``dot_general``
  equations of its jaxpr with each scan body counted once per iteration.
  Inside a ``shard_map`` the shapes are one device's, so the count is per
  device there.
"""

from __future__ import annotations

from typing import Iterable

import numpy as np
from jax.extend import core as jex_core


def _transformer_layer_params(c: dict) -> int:
    D, H, KV, Dh, F = (c["d_model"], c["n_heads"], c["n_kv_heads"],
                       c["head_dim"], c["d_ff"])
    attn = D * H * Dh * 2 + D * KV * Dh * 2
    mlp = D * F * (3 if c["gated_mlp"] else 2)
    return attn + mlp


def _ssm_dims(c: dict):
    di = c["ssm_expand"] * c["d_model"]
    return di, di // c["ssm_head_dim"], c["ssm_state"]


def _ssm_layer_matmul_params(c: dict) -> int:
    di, H, N = _ssm_dims(c)
    return c["d_model"] * (2 * di + 2 * N + H) + di * c["d_model"]


def param_count(c: dict) -> int:
    D, V, L = c["d_model"], c["vocab"], c["n_layers"]
    embed = V * D * (1 if c["tie_embed"] else 2)
    if c["family"] == "ssm":
        di, H, N = _ssm_dims(c)
        cc = di + 2 * N
        layer = (D + _ssm_layer_matmul_params(c) + c["ssm_conv"] * cc + cc
                 + 3 * H + di)
    else:
        layer = 2 * D + _transformer_layer_params(c)
    return L * layer + D + embed


def model_flops_per_step(c: dict, rows: int, seq: int) -> float:
    """Forward + backward matmul operations of one step of ``rows``
    sequences of ``seq`` positions each."""
    D, V, L = c["d_model"], c["vocab"], c["n_layers"]
    text = seq - c.get("n_patches", 0)
    if c["family"] == "ssm":
        di, H, N = _ssm_dims(c)
        P, Q = c["ssm_head_dim"], min(c["ssm_chunk"], seq)
        per_pos = (2 * _ssm_layer_matmul_params(c)
                   + 2 * c["ssm_conv"] * (di + 2 * N)
                   + Q * N             # C B^T inside the chunk, causal half
                   + Q * H * P         # (L o C B^T) X, causal half
                   + 2 * N * H * P     # chunk states
                   + 2 * N * H * P)    # states to outputs
    else:
        per_pos = (2 * _transformer_layer_params(c)
                   + 2 * c["n_heads"] * c["head_dim"] * seq)  # causal QK, PV
    fwd = L * per_pos * seq + 2 * D * V * text
    return 3.0 * fwd * rows


def _dot_flops(eqn) -> int:
    (lc, rc), _ = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    contract = int(np.prod([lhs[d] for d in lc])) if lc else 1
    out = int(np.prod(eqn.outvars[0].aval.shape))
    return 2 * out * contract


def _sub_jaxprs(eqn) -> Iterable:
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if isinstance(x, jex_core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jex_core.Jaxpr):
                yield x


def jaxpr_matmul_flops(jaxpr) -> float:
    """Executed matmul operations of a (closed) jaxpr."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    total = 0.0
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_flops(eqn)
        elif name == "cond":
            total += max(jaxpr_matmul_flops(b) for b in eqn.params["branches"])
        else:
            inner = sum(jaxpr_matmul_flops(j) for j in _sub_jaxprs(eqn))
            if name == "scan":
                inner *= eqn.params["length"]
            total += inner
    return total
