"""Pallas TPU kernel: fused paged decode-attention over an int8 KV cache.

The serving-side hot loop (``repro.serve``) keeps the KV cache as **int8
grid integers** in a paged pool — one page = one ⟨IL, FL⟩ group under the
``kv_cache`` precision domain, encoded by the grouped wire codec
(``dps_quant_group_wire_pallas`` / ``fixed_point.wire_quantize``).  The
naive decode step would dequantize the whole pool to fp32 in HBM before
attending (4× the pool bytes written + read back).  This kernel fuses the
dequantize into the attention read:

    grid = (batch_slot, page_slot); each step gathers ONE physical page of
    K and V straight from the int8 pool (the page table is an SMEM
    scalar-prefetch operand, so the gather is a BlockSpec index_map —
    ``ptab[b, p]`` — and changing page assignments never recompiles),
    multiplies by 2^-FL **in-register** (per-page FL from a second SMEM
    table), and folds the page into an online-softmax accumulator held in
    VMEM scratch.  HBM traffic per decoded token: the int8 pages of the
    sequence + the (tiny) fp32 q/out — the fp32 cache never exists in HBM.

Out-of-range page-table entries simply must point at a valid pool row (the
serve layer reserves a trash page); correctness comes from the sequence-
length mask, which zeroes every position ≥ ``lens[b]`` regardless of what
the gathered page contains.

``_paged_attn_jnp`` is the bit-exact portable reference (same math, same
op order, a ``lax.scan`` over page slots instead of the grid) — it is what
CPU serving runs, re-exported as ``kernels.ref.paged_decode_attn_ref``.
The kernel body is registered in ``dps_quant.KERNEL_SIGNATURES`` and its
call geometry is declared by ``ops.paged_attn_call_geometry`` so
``repro.analysis.kernel_checks`` covers it statically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.device import on_tpu
from repro.kernels.dps_quant import _exp2i

# matches models.attention.NEG_INF: finite, so masked-row softmax math
# stays NaN-free (exp(NEG_INF - m) underflows to exactly 0.0)
NEG_INF = -1e30


def _page_attn_step(q, kw, vw, fl_k, fl_v, base, seq_len, m, l, acc, *,
                    scale: float):
    """Fold one KV page into the online-softmax accumulator.

    Shared verbatim by the kernel body and the jnp reference so the two are
    bit-exact: identical op sequence on identical shapes.

    q: (KV, G, Dh) fp32 — the decode-step query for one batch row, its H
        heads grouped by the KV head that serves them (GQA, G = H / KV).
    kw/vw: (page, KV, Dh) int8 grid integers (or fp32 when paging runs at
        ``bits=None``; then FL = 0 and the dequant multiply is exact ×1.0).
    fl_k/fl_v: scalar int32 — this page's FL (per-page grid exponent).
    base: scalar int32 — first absolute position covered by this page.
    seq_len: scalar int32 — valid length of this row (positions ≥ len mask
        to NEG_INF, so trash-page garbage never reaches the output).
    m/l/acc: (KV, G, 1)/(KV, G, 1)/(KV, G, Dh) fp32 running max /
        normalizer / value.

    Both contractions batch over the KV head (leading on q, second on the
    page), so each keeps a non-contracting dimension on either side — the
    form Mosaic lowers.
    """
    ps = kw.shape[0]
    k = kw.astype(jnp.float32) * _exp2i(-fl_k)
    v = vw.astype(jnp.float32) * _exp2i(-fl_v)

    # f32 contractions at full precision on the MXU: the step is bound by
    # the page reads, and a one-pass bf16 product would round q and k to
    # 8 mantissa bits
    hp = (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)
    # scores (KV, G, ps): contract Dh, batch over KV
    s = jax.lax.dot_general(q, k, (((2,), (2,)), ((0,), (1,))),
                            precision=hp, preferred_element_type=jnp.float32)
    idx = base + jax.lax.broadcasted_iota(jnp.int32, (1, 1, ps), 2)
    valid = (idx < seq_len).astype(jnp.float32)
    s = s * scale + jnp.where(valid > 0.0, 0.0, NEG_INF)

    bm = jnp.max(s, axis=2, keepdims=True)
    new_m = jnp.maximum(m, bm)
    p = jnp.exp(s - new_m) * valid
    corr = jnp.exp(m - new_m)
    new_l = l * corr + jnp.sum(p, axis=2, keepdims=True)
    # pv (KV, G, Dh): contract ps, batch over KV
    pv = jax.lax.dot_general(p, v, (((2,), (0,)), ((0,), (1,))),
                             precision=hp, preferred_element_type=jnp.float32)
    new_acc = acc * corr + pv
    return new_m, new_l, new_acc


def _finalize(m, l, acc):
    # fully-masked rows (inactive batch slots) have l == 0 → output 0, not NaN
    return acc / jnp.maximum(l, 1e-30)


def _paged_attn_kernel(ptab_ref,    # SMEM: (B, P) int32 page table
                       fmt_ref,     # SMEM: (n_pages, 2) int32 [fl_k, fl_v]
                       lens_ref,    # SMEM: (B,) int32 valid sequence lengths
                       q_ref,       # VMEM: (1, KV, G, Dh) query block
                       k_ref,       # VMEM: (1, page, KV, Dh) gathered K page
                       v_ref,       # VMEM: (1, page, KV, Dh) gathered V page
                       out_ref,     # VMEM out: (1, KV, G, Dh) fp32
                       m_ref, l_ref, acc_ref,   # VMEM scratch accumulators
                       *, page_size: int, scale: float):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    phys = ptab_ref[b, p]
    m, l, acc = _page_attn_step(
        q_ref[0], k_ref[0], v_ref[0], fmt_ref[phys, 0], fmt_ref[phys, 1],
        p * page_size, lens_ref[b], m_ref[...], l_ref[...], acc_ref[...],
        scale=scale)
    m_ref[...] = m
    l_ref[...] = l
    acc_ref[...] = acc

    @pl.when(p == pl.num_programs(1) - 1)
    def _fin():
        out_ref[0] = _finalize(m_ref[...], l_ref[...],
                               acc_ref[...]).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def paged_attn_pallas(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      fmt: jax.Array, ptab: jax.Array, lens: jax.Array,
                      *, scale: float, interpret: bool = False):
    """Fused paged decode attention; one launch per decode step.

    ``q``: fp32 (B, H, Dh) single-token queries.  ``k_pages``/``v_pages``:
    (n_pages, page, KV, Dh) int8 pools (fp32 at ``bits=None``).  ``fmt``:
    int32 (n_pages, 2) per-page [FL_k, FL_v].  ``ptab``: int32 (B, P)
    logical→physical page table (entries past a row's last page must point
    at a valid pool row — masked by ``lens``).  ``lens``: int32 (B).
    Returns fp32 (B, H, Dh).
    """
    B, H, Dh = q.shape
    n_pages, ps, KV, _ = k_pages.shape
    G = H // KV
    P = ptab.shape[1]
    kernel = functools.partial(_paged_attn_kernel, page_size=ps, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, P),
            in_specs=[
                pl.BlockSpec((1, KV, G, Dh), lambda b, p, *_: (b, 0, 0, 0)),
                # the page gather: scalar-prefetch refs arrive as trailing
                # index_map args, so the block index is ptab[b, p]
                pl.BlockSpec((1, ps, KV, Dh),
                             lambda b, p, ptab, fmt, lens: (ptab[b, p], 0, 0, 0)),
                pl.BlockSpec((1, ps, KV, Dh),
                             lambda b, p, ptab, fmt, lens: (ptab[b, p], 0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, KV, G, Dh),
                                   lambda b, p, *_: (b, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((KV, G, 1), jnp.float32),
                pltpu.VMEM((KV, G, 1), jnp.float32),
                pltpu.VMEM((KV, G, Dh), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, G, Dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
    )(ptab, fmt, lens, q.reshape(B, KV, G, Dh), k_pages, v_pages)
    return out.reshape(B, H, Dh)


def _paged_attn_jnp(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    fmt: jax.Array, ptab: jax.Array, lens: jax.Array,
                    *, scale: float):
    """Bit-exact portable reference (and the CPU serving path).

    Python loop over batch rows + ``lax.scan`` over page slots, calling the
    SAME ``_page_attn_step`` on the same shapes as the kernel grid — so the
    interpret-mode kernel and this function agree bitwise.  Never
    materializes the dequantized pool: one page is decoded per scan step.
    """
    B, H, Dh = q.shape
    ps, KV = k_pages.shape[1:3]
    G = H // KV
    P = ptab.shape[1]

    def one_row(qb, ptab_b, len_b):
        def body(carry, p):
            m, l, acc = carry
            phys = ptab_b[p]
            kw = jax.lax.dynamic_index_in_dim(k_pages, phys, keepdims=False)
            vw = jax.lax.dynamic_index_in_dim(v_pages, phys, keepdims=False)
            m, l, acc = _page_attn_step(qb, kw, vw, fmt[phys, 0], fmt[phys, 1],
                                        p * ps, len_b, m, l, acc, scale=scale)
            return (m, l, acc), None

        init = (jnp.full((KV, G, 1), NEG_INF, jnp.float32),
                jnp.zeros((KV, G, 1), jnp.float32),
                jnp.zeros((KV, G, Dh), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(body, init, jnp.arange(P, dtype=jnp.int32))
        return _finalize(m, l, acc).reshape(H, Dh)

    # unrolled over B (small at serving batch sizes) rather than vmapped:
    # vmap batches the dot_generals into different contraction shapes, which
    # need not round identically to the kernel's per-row grid steps.
    q = q.reshape(B, KV, G, Dh)
    return jnp.stack([one_row(q[b], ptab[b], lens[b]) for b in range(B)])


def paged_decode_attn(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                      fmt: jax.Array, ptab: jax.Array, lens: jax.Array,
                      *, scale: float, backend: str = "auto",
                      interpret: bool | None = None):
    """Backend-dispatching entry point (same contract as the kernel).

    ``backend``: "kernel" (Pallas; interpret off-TPU), "jnp" (the scan
    reference), or "auto" (kernel on TPU, jnp elsewhere — interpret-mode
    Pallas inside the serving loop would pay a per-step lowering tax).
    """
    if backend == "auto":
        backend = "kernel" if on_tpu() else "jnp"
    if backend == "kernel":
        if interpret is None:
            interpret = not on_tpu()
        return paged_attn_pallas(q, k_pages, v_pages, fmt, ptab, lens,
                                 scale=scale, interpret=interpret)
    if backend != "jnp":
        raise ValueError(f"unknown paged-attention backend {backend!r}")
    return _paged_attn_jnp(q, k_pages, v_pages, fmt, ptab, lens, scale=scale)
