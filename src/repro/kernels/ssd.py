"""Pallas TPU kernels: the SSD scan's output from each chunk's inputs and
starting state, forward and backward, with every Q x Q block kept in VMEM.

Within one chunk of Q steps and for head h, Mamba2's SSD (Dao & Gu '24)
writes the output as the chunk's own inputs through a masked,
decay-weighted product, plus the state the chunk starts from:

    y[q, h, :] = sum_{k <= q} (C_q . B_k) exp(cum[q, h] - cum[k, h]) xdt[k, h, :]
                 + exp(cum[q, h]) h0[h] C_q

with ``cum`` the running sum of the log-decays inside the chunk and
``h0[h]`` the (P, N) state.  In jnp (``models/ssm.py`` ``_ssd_chunks``)
the segment sums, the decays and their product with C Bᵀ are (B, nc, Q,
Q, H) float32 tensors, and so are their cotangents in the backward; here
they live in VMEM only.  HBM traffic per call is the inputs once and the
outputs once:

    forward   read xdt, B, C, cum, h0;      write y (x's dtype)
    backward  read xdt, B, C, cum, h0, dy;  write dxdt, dB, dC, dcum, dh0

The chunk states and the recurrence that gives each chunk's h0 stay with
the caller.

Layout: features by positions.  The kernels read xdt as (batch, H·P, S)
and B, C as (batch, N, S), and write y as (batch, H·P, S): positions on
the lanes.  That is the layout the compiled train step keeps the mixer's
activations in (the causal conv puts S minor), so the transposes around
the calls are free; kernels on (S, H·P) blocks cost a relayout copy of
x, y and dy on every pass.  The states are read as the caller's scan
stacks them, (chunks, batch, H·P, N).  Per head the forward is
yᵀ = xdtᵀ Mᵀ + exp(cum) h0 Cᵀ, with Mᵀ[k, q] = (B_k . C_q)
exp(cum_q - cum_k) for k <= q.

Grid: (batch, chunks, head blocks of ``HEAD_BLOCK`` heads, that is
``HEAD_BLOCK·P`` rows of xdtᵀ).  B Cᵀ is computed once per chunk, at head
block 0, into VMEM scratch; the backward also sums d(B Cᵀ) and the state
term's dC over the head blocks there and forms dB and dC at the last
one, so the head-block axis runs in order.  The (k, q) tiles in which
every k > q are skipped.  On the diagonal tiles the segment sum is
positive above it and may pass 88.7 over one chunk, so it is masked to
-inf before ``exp``, as the jnp form does.

Numerics: every product and sum is float32, and every dot accumulates in
float32.  The dots of the quadratic form cast their operands to
``dot_dtype``: ``bfloat16`` is what the compiled jnp form's dots there
take.  The states and their cotangents are float32, and the jnp form's
state einsum takes them as float32 operands, so they enter the state
term's dots in two bfloat16 parts (``_dot_split``), which round them no
more coarsely whatever precision XLA gives those.  ``dot_dtype``
``float32`` keeps every operand whole, which is how the tests hold the
kernels to the jnp form at 1e-4.

``intra_fwd`` and ``intra_bwd`` are the two calls, each a named
``pallas_call`` (``ssd_intra_fwd``, ``ssd_intra_bwd``); ``models/ssm.py``
joins them into one differentiable op.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

HEAD_BLOCK = 8
TILE = 128                  # the Q x Q form's tiles, a lane width
SUBLANES = 16               # rows of one bfloat16 tile
F32 = jnp.float32

_NN = (((1,), (0,)), ((), ()))      # a · b
_NT = (((1,), (1,)), ((), ()))      # a · bᵀ
_TN = (((0,), (0,)), ((), ()))      # aᵀ · b


def fits(Q: int, H: int, P: int) -> bool:
    """Whether the kernels tile a chunk of ``Q`` steps of ``H`` heads of
    width ``P`` on the chip: whole 128-lane tiles of positions, whole head
    blocks, and each head's rows whole bfloat16 tiles."""
    return Q % TILE == 0 and H % HEAD_BLOCK == 0 and P % SUBLANES == 0


def _dot(a, b, dims, dtype):
    return jax.lax.dot_general(a.astype(dtype), b.astype(dtype), dims,
                               preferred_element_type=F32)


def _parts(a, dtype):
    """``a`` as a sum of ``dtype`` values: a float32 ``a`` in two, its
    rounding and the rounding of the rest (about 2^-17 of ``a`` left out
    for bfloat16); an ``a`` already in ``dtype`` as itself."""
    hi = a.astype(dtype)
    if a.dtype == dtype or dtype == F32:
        return [hi]
    return [hi, (a - hi.astype(F32)).astype(dtype)]


def _dot_split(a, b, dims, dtype):
    """``_dot`` with float32 operands split by ``_parts``, all products but
    lo·lo: bfloat16 passes that keep float32 operands to about 2^-17."""
    return sum(_dot(x, y, dims, dtype)
               for i, x in enumerate(_parts(a, dtype))
               for j, y in enumerate(_parts(b, dtype)) if i + j < 2)


def _tiles(Q: int):
    """The (k, q) tiles of a chunk's Q x Q form that hold some k <= q, each
    with whether it holds the diagonal."""
    n = Q // TILE
    return [(slice(k * TILE, (k + 1) * TILE), slice(q * TILE, (q + 1) * TILE),
             k == q) for q in range(n) for k in range(q + 1)]


def _decay(col, row, diag):
    """exp(cum_q - cum_k) over one (k, q) tile, from the k's cum as a (T, 1)
    column and the q's as a (1, T) row; on a diagonal tile, zero where
    k > q (masked to -inf before ``exp``)."""
    rel = row - col
    if diag:
        k = jax.lax.broadcasted_iota(jnp.int32, rel.shape, 0)
        q = jax.lax.broadcasted_iota(jnp.int32, rel.shape, 1)
        rel = jnp.where(k <= q, rel, -jnp.inf)
    return jnp.exp(rel)


def _add(acc, key, v):
    acc[key] = acc[key] + v if key in acc else v


def _fwd_kernel(b_ref, c_ref, x_ref, h_ref, col_ref, row_ref, y_ref,
                bc_ref, *, P, dot_dtype):
    @pl.when(pl.program_id(2) == 0)
    def _():
        bc_ref[...] = _dot(b_ref[...], c_ref[...], _TN, dot_dtype)

    for j in range(HEAD_BLOCK):
        rows = slice(j * P, (j + 1) * P)
        # the chunk's starting state: yᵀ = exp(cum_q) h Cᵀ
        y = _dot_split(h_ref[rows], c_ref[...], _NN, dot_dtype) * jnp.exp(
            row_ref[j:j + 1])
        y = {t: y[:, t:t + TILE] for t in range(0, y.shape[1], TILE)}
        for k, q, diag in _tiles(bc_ref.shape[0]):
            m = bc_ref[k, q] * _decay(col_ref[k, j:j + 1],
                                      row_ref[j:j + 1, q], diag)
            _add(y, q.start, _dot(x_ref[rows, k], m, _NN, dot_dtype))
        for start, v in y.items():
            y_ref[rows, start:start + TILE] = v.astype(y_ref.dtype)


def _bwd_kernel(b_ref, c_ref, x_ref, h_ref, dy_ref, col_ref, row_ref,
                dx_ref, db_ref, dc_ref, dh_ref, dcol_ref, drow_ref,
                bc_ref, dbc_ref, dc_acc, *, P, dot_dtype):
    h = pl.program_id(2)

    @pl.when(h == 0)
    def _():
        bc_ref[...] = _dot(b_ref[...], c_ref[...], _TN, dot_dtype)
        dbc_ref[...] = jnp.zeros_like(dbc_ref)
        dc_acc[...] = jnp.zeros_like(dc_acc)

    for j in range(HEAD_BLOCK):
        rows = slice(j * P, (j + 1) * P)
        # the starting state's term: dh = (dy∘e) C, dCᵀ += hᵀ (dy∘e) and
        # d cum_q += sum_p dy∘yᵀ_state, with e = exp(cum_q)
        dye = dy_ref[rows].astype(F32) * jnp.exp(row_ref[j:j + 1])
        dh_ref[rows] = _dot_split(dye, c_ref[...], _NT, dot_dtype)
        dc_acc[...] += _dot_split(h_ref[rows], dye, _TN, dot_dtype)
        da_state = (_dot_split(h_ref[rows], c_ref[...], _NN, dot_dtype)
                    * dye).sum(axis=0, keepdims=True)
        drow = {t: da_state[:, t:t + TILE]
                for t in range(0, da_state.shape[1], TILE)}
        dx, dcol = {}, {}
        for k, q, diag in _tiles(bc_ref.shape[0]):
            decay = _decay(col_ref[k, j:j + 1], row_ref[j:j + 1, q], diag)
            bc = bc_ref[k, q]
            dy = dy_ref[rows, q]
            # dxdtᵀ = dyᵀ M and dMᵀ = xdt dyᵀ, one tile at a time
            _add(dx, k.start, _dot(dy, bc * decay, _NT, dot_dtype))
            dm_decay = _dot(x_ref[rows, k], dy, _TN, dot_dtype) * decay
            dbc_ref[k, q] += dm_decay
            # d cum_q = sum_k dM∘M[k, q] and d cum_k = -sum_q dM∘M[k, q]
            # (the latter summed over the q tiles before across the lanes)
            da = dm_decay * bc
            _add(drow, q.start, da.sum(axis=0, keepdims=True))
            _add(dcol, k.start, da)
        for start, v in dx.items():
            dx_ref[rows, start:start + TILE] = v.astype(dx_ref.dtype)
        for start, v in drow.items():
            drow_ref[j:j + 1, start:start + TILE] = v
        for start, v in dcol.items():
            dcol_ref[start:start + TILE, j:j + 1] = v.sum(axis=1,
                                                          keepdims=True)

    @pl.when(h == pl.num_programs(2) - 1)
    def _():
        dbc = dbc_ref[...]
        db_ref[...] = _dot(c_ref[...], dbc, _NT,
                           dot_dtype).astype(db_ref.dtype)
        dc_ref[...] = (_dot(b_ref[...], dbc, _NN, dot_dtype)
                       + dc_acc[...]).astype(dc_ref.dtype)


def _cum_views(cum):
    """(batch, S, H) -> each head block's cum as (batch, H/HEAD_BLOCK, S,
    HEAD_BLOCK) columns, and all heads' as (batch, H, S) rows."""
    Bt, S, H = cum.shape
    cols = cum.reshape(Bt, S, H // HEAD_BLOCK, HEAD_BLOCK).transpose(0, 2, 1, 3)
    return cols, cum.transpose(0, 2, 1)


def _grid(x, b, cum, Q):
    """The grid of (batch row, chunk, head block) steps, the head width P,
    and the blocks of one step: B or C, a head block's rows, its cum
    columns, its cum rows and its starting states."""
    Bt, HP, S = x.shape
    N, H = b.shape[1], cum.shape[-1]
    P, hb = HP // H, HEAD_BLOCK
    feats = pl.BlockSpec((None, N, Q), lambda i, c, h: (i, 0, c))
    heads = pl.BlockSpec((None, hb * P, Q), lambda i, c, h: (i, h, c))
    col = pl.BlockSpec((None, None, Q, hb), lambda i, c, h: (i, h, c, 0))
    row = pl.BlockSpec((None, hb, Q), lambda i, c, h: (i, h, c))
    state = pl.BlockSpec((None, None, hb * P, N), lambda i, c, h: (c, i, h, 0))
    return (Bt, S // Q, H // hb), P, (feats, heads, col, row, state)


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))


def intra_fwd(x, b, c, cum, h, chunk, *, dot_dtype=jnp.bfloat16,
              interpret=False):
    """The scan's output over chunks of ``chunk`` steps, in x's dtype: the
    intra-chunk term plus each chunk's starting state's term.

    x: (batch, H·P, S) the inputs times dt, heads major over their P rows;
    b, c: (batch, N, S); cum: (batch, S, H) float32, the log-decays'
    running sum inside each chunk; h: (chunks, batch, H·P, N) float32,
    the state each chunk starts from.  Returns y (batch, H·P, S)."""
    grid, P, (feats, heads, col, row, state) = _grid(x, b, cum, chunk)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, P=P, dot_dtype=dot_dtype),
        grid=grid,
        in_specs=[feats, feats, heads, state, col, row],
        out_specs=heads,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((chunk, chunk), F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssd_intra_fwd",
    )(b, c, x, h, *_cum_views(cum))


def intra_bwd(x, b, c, cum, h, dy, chunk, *, dot_dtype=jnp.bfloat16,
              interpret=False):
    """The gradients of ``intra_fwd``'s output with respect to x, b, c, cum
    and h, each in its own dtype, from its cotangent ``dy`` (batch, H·P,
    S)."""
    grid, P, (feats, heads, col, row, state) = _grid(x, b, cum, chunk)
    Bt, S, H = cum.shape
    N = b.shape[1]
    dx, db, dc, dh, dcol, drow = pl.pallas_call(
        functools.partial(_bwd_kernel, P=P, dot_dtype=dot_dtype),
        grid=grid,
        in_specs=[feats, feats, heads, state, heads, col, row],
        out_specs=[heads, feats, feats, state, col, row],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(b.shape, b.dtype),
                   jax.ShapeDtypeStruct(c.shape, c.dtype),
                   jax.ShapeDtypeStruct(h.shape, F32),
                   jax.ShapeDtypeStruct((Bt, H // HEAD_BLOCK, S, HEAD_BLOCK),
                                        F32),
                   jax.ShapeDtypeStruct((Bt, H, S), F32)],
        scratch_shapes=[pltpu.VMEM((chunk, chunk), F32),
                        pltpu.VMEM((chunk, chunk), F32),
                        pltpu.VMEM((N, chunk), F32)],
        compiler_params=_params(),
        interpret=interpret,
        name="ssd_intra_bwd",
    )(b, c, x, h, dy, *_cum_views(cum))
    dcum = drow.transpose(0, 2, 1) - dcol.transpose(0, 2, 1, 3).reshape(
        cum.shape)
    return dx, db, dc, dcum, dh
