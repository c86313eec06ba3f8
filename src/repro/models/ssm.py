"""Mamba2 (SSD — state-space duality) block, chunked-scan formulation.

Implements the SSD algorithm of Dao & Gu '24 (arXiv:2405.21060): the
sequence is split into chunks of ``ssm_chunk``; within a chunk the output is
a masked (decay-weighted) attention-like matmul, across chunks a small
recurrence carries the (H, P, N) state.  Train/prefill cost is
O(S·Q·(P+N)) — sub-quadratic in S — and decode is an O(1) state update,
which is why the ssm and hybrid configs set ``supports_long``.  The scan's
body runs under the ``ssm.scan`` name scope, so its device time can be told
from the projections' in a profile.  On a single TPU the intra-chunk
quadratic form runs as the Pallas kernels of ``kernels/ssd.py`` where their
tiles fit; everywhere else, and as their reference, in jnp.

Numerics: the recurrent state, per-step decays, A_log and dt_bias stay fp32
(policy carve-out — fixed-point emulation of a 500k-step recurrence
underflows at 2^-FL; the paper's §5 anticipates exactly this failure mode).
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.device import on_tpu
from repro.dist.sharding import current_mesh_rules, logical_constraint
from repro.kernels import ssd as ssd_kernel
from repro.models.common import ParamDef, rms_norm

SCAN_SCOPE = "ssm.scan"     # the scan's ops, for a profile (bench/ssm_scan.py)


def d_inner(cfg: ModelConfig) -> int:
    return cfg.ssm_expand * cfg.d_model


def n_ssm_heads(cfg: ModelConfig) -> int:
    return d_inner(cfg) // cfg.ssm_head_dim


def conv_channels(cfg: ModelConfig) -> int:
    return d_inner(cfg) + 2 * cfg.ssm_state


def ssm_defs(cfg: ModelConfig, dtype) -> Dict[str, ParamDef]:
    D, N = cfg.d_model, cfg.ssm_state
    di, H = d_inner(cfg), n_ssm_heads(cfg)
    cc = conv_channels(cfg)
    return {
        # in_proj emits [z, x, B, C, dt]
        "w_in": ParamDef((D, 2 * di + 2 * N + H), ("fsdp", "tp"), dtype=dtype),
        "conv_w": ParamDef((cfg.ssm_conv, cc), (None, "tp"), scale=1.0, dtype=dtype),
        "conv_b": ParamDef((cc,), ("tp",), init="zeros", dtype=dtype),
        "a_log": ParamDef((H,), (None,), init="zeros", dtype=jnp.float32),
        "dt_bias": ParamDef((H,), (None,), init="zeros", dtype=jnp.float32),
        "d_skip": ParamDef((H,), (None,), init="ones", dtype=jnp.float32),
        "norm_scale": ParamDef((di,), ("tp",), init="ones", dtype=jnp.float32),
        "w_out": ParamDef((di, D), ("tp", "fsdp"), dtype=dtype),
    }


def _split_proj(cfg: ModelConfig, zxbcdt: jax.Array):
    di, N, H = d_inner(cfg), cfg.ssm_state, n_ssm_heads(cfg)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:di + di + 2 * N]
    dt = zxbcdt[..., di + di + 2 * N:]
    return z, xbc, dt


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array,
                 state: Optional[jax.Array] = None):
    """Depthwise causal conv over seq.  xbc (B,S,C), w (K,C).

    With ``state`` (B, K-1, C) — decode path — prepends the cached tail and
    returns the updated tail."""
    K = w.shape[0]
    if state is not None:
        full = jnp.concatenate([state.astype(xbc.dtype), xbc], axis=1)
    else:
        full = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    windows = jnp.stack([full[:, i:i + xbc.shape[1]] for i in range(K)], 0)
    out = jnp.einsum("kbsc,kc->bsc", windows, w) + b
    new_state = full[:, -(K - 1):] if K > 1 else None
    return jax.nn.silu(out), new_state


def _decays(cfg: ModelConfig, dt_raw: jax.Array, a_log: jax.Array,
            dt_bias: jax.Array):
    """Per-(step, head) dt and log-decay, fp32.  dt_raw (..., H)."""
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + dt_bias)
    a = -jnp.exp(a_log.astype(jnp.float32))
    log_decay = dt * a                      # <= 0
    return dt, log_decay


def ssd_scan(cfg: ModelConfig, x: jax.Array, b_mat: jax.Array, c_mat: jax.Array,
             dt: jax.Array, log_decay: jax.Array,
             h0: Optional[jax.Array] = None):
    """Chunked SSD.  x (B,S,H,P); b,c (B,S,N); dt/log_decay (B,S,H) fp32.

    Returns (y (B,S,H,P), h_final (B,H,P,N) fp32)."""
    B, S, H, Pd = x.shape
    N = b_mat.shape[-1]
    Q = min(cfg.ssm_chunk, S)
    S_orig = S
    if S % Q:
        # pad to the chunk grid: zero x/B/C (no state contribution) and zero
        # log_decay (decay factor 1 — final state unaffected)
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        log_decay = jnp.pad(log_decay, ((0, 0), (0, pad), (0, 0)))
        S = S + pad
    nc = S // Q

    intra = ssd_intra if intra_kernel_fits(Q, H, Pd) else None
    with jax.named_scope(SCAN_SCOPE):
        y, h_final = _ssd_chunks(x, b_mat, c_mat, dt, log_decay, h0, nc, Q,
                                 intra)
    return y[:, :S_orig].astype(x.dtype), h_final


def intra_kernel_fits(Q: int, H: int, P: int) -> bool:
    """Whether ``ssd_scan`` runs the intra-chunk form as the Pallas kernels:
    on a TPU, with no multi-device mesh (under one, a ``pallas_call`` is not
    partitioned), at a chunk and head width the kernels tile."""
    mesh, _ = current_mesh_rules()
    return (on_tpu() and (mesh is None or mesh.devices.size == 1)
            and ssd_kernel.fits(Q, H, P))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def ssd_intra(x, b, c, cum, h, chunk, dot_dtype=jnp.bfloat16):
    """The scan's output as ``kernels/ssd.py`` computes it from the states
    each chunk starts from, in x's dtype, over (B, features, S) views (see
    ``intra_fwd``).  Off a TPU the kernels run in the Pallas interpreter."""
    return ssd_kernel.intra_fwd(x, b, c, cum, h, chunk, dot_dtype=dot_dtype,
                                interpret=not on_tpu())


# The rules are traced apart from the caller's name stack, where a scope
# reaches an op only as ``jvp(ssm.scan)``; each opens the scan's scope
# itself, so that the kernels' own ``op_name`` carries it.
def _ssd_intra_fwd(x, b, c, cum, h, chunk, dot_dtype):
    with jax.named_scope(SCAN_SCOPE):
        y = ssd_kernel.intra_fwd(x, b, c, cum, h, chunk, dot_dtype=dot_dtype,
                                 interpret=not on_tpu())
    return y, (x, b, c, cum, h)


def _ssd_intra_bwd(chunk, dot_dtype, res, dy):
    with jax.named_scope(SCAN_SCOPE):
        return ssd_kernel.intra_bwd(*res, dy, chunk, dot_dtype=dot_dtype,
                                    interpret=not on_tpu())


ssd_intra.defvjp(_ssd_intra_fwd, _ssd_intra_bwd)


def _ssd_chunks(x, b_mat, c_mat, dt, log_decay, h0, nc: int, Q: int,
                intra=None):
    """The scan over ``nc`` whole chunks of ``Q`` steps: the intra-chunk
    quadratic form, the chunk states, the recurrence over chunks and the
    states' contribution to each position.  ``intra`` computes the
    quadratic form and the states' term from the states each chunk starts
    from (``ssd_intra``); None forms both in jnp, with the quadratic
    form's (B, nc, Q, Q, H) tensors in memory.

    Every ``exp`` takes a sum of log-decays (each <= 0), so it is at most
    1.  Above the diagonal the intra-chunk segment sum cum_t - cum_s is
    positive instead; it is masked to -inf before the ``exp``, because past
    a summed decay of 88.7 in one chunk it overflows to inf, and inf * 0 is
    NaN in the values and in the gradients."""
    B, S, H, Pd = x.shape
    N = b_mat.shape[-1]

    xr = (x * dt[..., None].astype(x.dtype)).reshape(B, nc, Q, H, Pd)
    br = b_mat.reshape(B, nc, Q, N)
    cr = c_mat.reshape(B, nc, Q, N)
    ld = log_decay.reshape(B, nc, Q, H)
    # heads shard on the model axis (B/C are head-shared and stay replicated);
    # the O(Q²·H) intra-chunk tensors below are the SSD memory hot spot
    xr = logical_constraint(xr, "batch", None, None, "heads", None)
    ld = logical_constraint(ld, "batch", None, None, "heads")
    cum = jnp.cumsum(ld, axis=2)                        # (B,nc,Q,H)
    total = cum[:, :, -1]                               # (B,nc,H)

    # --- intra-chunk (quadratic in Q only) ---
    if intra is None:
        cb = jnp.einsum("bcqn,bckn->bcqk", cr.astype(jnp.float32),
                        br.astype(jnp.float32))
        rel = cum[:, :, :, None, :] - cum[:, :, None, :, :]  # (B,nc,Q,Q,H)
        tri = jnp.tril(jnp.ones((Q, Q), bool))[None, None, :, :, None]
        decay_m = jnp.exp(jnp.where(tri, rel, -jnp.inf))
        m = cb[..., None] * decay_m                          # (B,nc,Q,Q,H)
        y_intra = jnp.einsum("bcqkh,bckhp->bcqhp", m, xr.astype(jnp.float32))

    # --- chunk states ---
    w_state = jnp.exp(total[:, :, None, :] - cum)        # (B,nc,Q,H)
    s_chunk = jnp.einsum("bckn,bckh,bckhp->bchpn", br.astype(jnp.float32),
                         w_state, xr.astype(jnp.float32))

    # --- inter-chunk recurrence ---
    if h0 is None:
        h0 = jnp.zeros((B, H, Pd, N), jnp.float32)

    def step(h, inp):
        s_c, tot = inp                                   # (B,H,P,N), (B,H)
        y_prev_state = h                                 # state before chunk
        h_next = jnp.exp(tot)[..., None, None] * h + s_c
        return h_next, y_prev_state

    (h_final, h_prevs) = jax.lax.scan(
        step, h0, (jnp.moveaxis(s_chunk, 1, 0), jnp.moveaxis(total, 1, 0)))

    if intra is not None:
        # the kernels take (features, positions) views: the layout the
        # compiled step keeps these activations in; the states as the
        # scan stacks them
        t = lambda a: a.reshape(B, S, -1).transpose(0, 2, 1)
        y = intra(t(xr), t(br), t(cr), cum.reshape(B, S, H),
                  h_prevs.reshape(nc, B, H * Pd, N), Q)
        return y.transpose(0, 2, 1).reshape(B, S, H, Pd), h_final
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)                # (B,nc,H,P,N)
    y_inter = jnp.einsum("bcqn,bcqh,bchpn->bcqhp", cr.astype(jnp.float32),
                         jnp.exp(cum), h_prevs)
    return (y_intra + y_inter).reshape(B, S, H, Pd), h_final


def ssm_apply(cfg: ModelConfig, p: Dict[str, jax.Array], x: jax.Array, *,
              mode: str = "train",
              cache: Optional[Tuple[jax.Array, jax.Array]] = None):
    """Mamba2 mixer.  cache = (ssm_state (B,H,P,N) fp32, conv_tail (B,K-1,C)).

    Returns (out (B,S,D), new_cache)."""
    B, S, D = x.shape
    di, N, H, Pd = d_inner(cfg), cfg.ssm_state, n_ssm_heads(cfg), cfg.ssm_head_dim

    zxbcdt = jnp.einsum("bsd,dk->bsk", x, p["w_in"])
    z, xbc, dt_raw = _split_proj(cfg, zxbcdt)
    zxbcdt = logical_constraint(zxbcdt, "batch", "seq", "tp")

    conv_state = cache[1] if cache is not None else None
    xbc, new_conv = _causal_conv(xbc, p["conv_w"], p["conv_b"], conv_state)
    xi = xbc[..., :di].reshape(B, S, H, Pd)
    b_mat = xbc[..., di:di + N]
    c_mat = xbc[..., di + N:]

    dt, log_decay = _decays(cfg, dt_raw, p["a_log"], p["dt_bias"])

    if mode == "decode":
        # O(1) recurrence: h = exp(dt·A)·h + dt·B⊗x  (S == 1)
        h = cache[0]
        a = jnp.exp(log_decay[:, 0])                     # (B,H)
        xu = (xi[:, 0].astype(jnp.float32) * dt[:, 0][..., None])
        h_new = (a[..., None, None] * h
                 + jnp.einsum("bhp,bn->bhpn", xu, b_mat[:, 0].astype(jnp.float32)))
        y = jnp.einsum("bn,bhpn->bhp", c_mat[:, 0].astype(jnp.float32), h_new)
        y = y[:, None].astype(x.dtype)                   # (B,1,H,P)
        new_cache = (h_new, new_conv)
    else:
        h0 = cache[0] if cache is not None else None
        y, h_final = ssd_scan(cfg, xi, b_mat, c_mat, dt, log_decay, h0)
        new_cache = (h_final, new_conv) if mode == "prefill" else None

    y = y + p["d_skip"].astype(y.dtype)[None, None, :, None] * xi
    y = y.reshape(B, S, di)
    y = rms_norm(y * jax.nn.silu(z), p["norm_scale"])
    out = jnp.einsum("bsk,kd->bsd", y, p["w_out"])
    return logical_constraint(out, "batch", "seq", "embed"), new_cache


def count_ssm_params(cfg: ModelConfig) -> int:
    D, N = cfg.d_model, cfg.ssm_state
    di, H = d_inner(cfg), n_ssm_heads(cfg)
    cc = conv_channels(cfg)
    return (D * (2 * di + 2 * N + H) + cfg.ssm_conv * cc + cc
            + 3 * H + di + di * D)
