#!/usr/bin/env python3
"""Chip smoke test: the main paths once on a TPU, at llama3_2_3b's widths.

    python chip_smoke.py              one chip: kernels, SSD scan, train,
                                      serve
    python chip_smoke.py --chips 4    four chips: data-parallel training with
                                      the int8 gradient wire against fp32
    python chip_smoke.py --rehearse   the same phases on the CPU at smoke
                                      widths, kernels in interpret mode

Everything runs in this one process: a TPU belongs to the process that
first touches JAX.  The widths are llama3_2_3b's published ones (d_model
3072, 24 query and 8 KV heads, head_dim 128, d_ff 8192, vocab 128256); only
the depth is cut, to 4 layers.  Weights are random from a seed.

The device is printed first, then one line per phase.  The exit code is
nonzero when a phase fails or no TPU is found.  On a chip the last line of
standard output is ``{"ok": true, "device": {"platform": "tpu", ...}}``; a
rehearsal reports the CPU it ran on.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import glob
import json
import math
import os
import shutil
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
HLO_DUMP = os.path.join(ROOT, "chiprun_out", "chip_smoke_hlo")

ARCH = "llama3_2_3b"
LAYERS = 4
# The int8 wire step holds several gradient-sized f32 buffers (aligned
# grads, rounding bits, decoded mean); at 4 layers the compiler puts it at
# 21 GB of a v5e's 16, at 2 layers it fits.
LAYERS_4CHIP = 2
D_MODEL, N_HEADS, N_KV, HEAD_DIM, D_FF = 3072, 24, 8, 128, 8192

# Tolerances, each with its reason.
# Stats are f32 sums over up to 2.5e7 terms; the kernel adds per-tile sums
# in grid order and XLA reduces as a tree, so they agree to rounding only.
STATS_RTOL = 1e-4
# Paged attention contracts in f32 (HIGHEST) in both the kernel and the
# reference; what differs is the order of accumulation and exp.  A one-pass
# bf16 contraction would miss this by ~30x (2^-9 per product).
ATTN_RTOL = 1e-4
# On-chip PRNG: per-element rounding error lies in (-1, 1) grid steps with
# mean 0 and std <= 0.5, so the mean over >= 2.6e5 elements has std <= 1e-3
# steps; 1e-2 is ten of those, while a stuck generator gives +-0.5.
PRNG_MEAN_ERR_STEPS = 1e-2
# ... and its abs-error sum matches the bits-operand run's to sampling
# noise (relative std ~ 1/sqrt(N)).
PRNG_ABS_ERR_RTOL = 2e-2
# SSD scan: the program rounds x * dt to bf16 (2^-9) before its f32
# einsums, which the chip runs at its default matmul precision, while the
# reference keeps f32 at HIGHEST.  Over sums of up to 256 products with
# random signs that leaves 4.0e-3 (values) and 6.0e-3 (gradient) of the
# largest value on the CPU at the small shapes; 2e-2 leaves room for the
# chip's precision, and an overflow shows as inf or NaN.
SSD_RTOL = 2e-2
# The scan's kernels against its jnp form: both round the same operands
# to bf16, but sum in other orders, so their float32 outputs differ in
# the last bits, and where such a pair straddles a bf16 rounding boundary
# the outputs (y, dx, dB, dC leave in bf16) land one unit apart: at the
# largest value, 2^-8 of it.  The kernels' gap may pass the jnp form's by
# that much; a v5e reads y 5.90e-3 against 4.37e-3, the gradients equal.
SSD_SLACK = 2 ** -8
# Four-chip check: the int8 wire adds rounding noise of at most one wire
# grid step per gradient element; over a few AdamW steps the two loss
# curves must stay within 1% of each other at every step.
WIRE_LOSS_RTOL = 1e-2


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


class _Tee:
    """Copy what a phase prints into a buffer while it still goes out."""

    def __init__(self, out):
        self.out, self.parts = out, []

    def write(self, s):
        self.parts.append(s)
        return self.out.write(s)

    def flush(self):
        self.out.flush()

    def text(self):
        return "".join(self.parts)


def _run_phase(name, fn, results):
    t0 = time.perf_counter()
    try:
        detail, ok = fn(), True
    except (Exception, SystemExit) as e:   # a CLI's SystemExit is a failure
        traceback.print_exc()
        detail, ok = f"{type(e).__name__}: {e}", False
    dt = time.perf_counter() - t0
    print(f"phase {name}: {'ok' if ok else 'FAILED'} ({dt:.1f} s wall) "
          f"{detail}", flush=True)
    results[name] = ok


# ---------------------------------------------------------------------------
# kernels: every Pallas kernel against its jnp reference
# ---------------------------------------------------------------------------

def kernels_phase(small: bool):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.device import on_tpu
    from repro.kernels import dps_quant as dq
    from repro.kernels import ref
    from repro.kernels.paged_attn import _paged_attn_jnp, paged_attn_pallas

    interpret = not on_tpu()
    keys = iter(jax.random.split(jax.random.key(0), 32))
    out = []

    def stats_close(a, b, what):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        rel = np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-30))
        _check(rel <= STATS_RTOL, f"{what}: stats rel diff {rel:.2e}")
        return rel

    # global quantize (emulation) and wire quantize, bits operand
    w_shape = (256, 1024) if small else (D_MODEL, D_FF)       # an MLP weight
    for wire, scale, (il, fl) in ((False, 0.02, (1, 12)),
                                  (True, 0.5, (2, 6))):
        kern = dq.dps_quant_wire_pallas if wire else dq.dps_quant_pallas
        oracle = ref.dps_quant_wire_ref if wire else ref.dps_quant_ref
        x = jax.random.normal(next(keys), w_shape) * scale
        bits = jax.random.bits(next(keys), w_shape, jnp.uint32)
        fmt3 = jnp.array([il, fl, 0], jnp.int32)
        for mode in ("nearest", "stochastic"):
            q_k, v_k = kern(x, fmt3, bits, stochastic=mode == "stochastic",
                            interpret=interpret)
            q_r, v_r = oracle(x, il, fl, bits, mode=mode)
            bad = int(jnp.sum(q_k != q_r))
            _check(bad == 0, f"{kern.__name__} {mode}: {bad} elements differ")
            rel = stats_close(v_k, v_r, f"{kern.__name__} {mode}")
            out.append(f"{'wire' if wire else 'quant'}/{mode} exact "
                       f"(stats {rel:.1e})")

        # on-chip PRNG: unbiased, and the same stats as the bits operand
        if not interpret:
            fmt3 = jnp.array([il, fl, 12345], jnp.int32)
            q_p, v_p = kern(x, fmt3, bits, stochastic=True,
                            use_onchip_prng=True)
            _, v_b = kern(x, fmt3, bits, stochastic=True)
            step = 2.0 ** -fl
            lo, hi = -(2.0 ** (il - 1)), 2.0 ** (il - 1) - step
            deq = q_p.astype(jnp.float32) * (step if wire else 1.0)
            err = float(jnp.mean(deq - jnp.clip(x, lo, hi))) / step
            _check(abs(err) <= PRNG_MEAN_ERR_STEPS,
                   f"prng {'wire' if wire else 'quant'}: mean rounding "
                   f"error {err:.2e} steps")
            for i in (0, 2, 6):          # count, overflow, max|x|
                _check(float(v_p[i]) == float(v_b[i]),
                       f"prng stat {i}: {float(v_p[i])} vs {float(v_b[i])}")
            rel = abs(float(v_p[3]) / float(v_b[3]) - 1.0)
            _check(rel <= PRNG_ABS_ERR_RTOL,
                   f"prng abs-err sum differs by {rel:.2e}")
            out.append(f"{'wire' if wire else 'quant'}/prng mean err "
                       f"{err:+.1e} steps")

    # the train step's tree passes: the fused leaf kernel over the leaves
    # of the benchmark's one-layer InternVL2-26B language model, against
    # the jnp path: unbiased, and the same count, overflow and max
    out.append(tree_quant_check(small, next(keys)))

    # grouped wire: one layer's attention weights, one format per leaf
    sizes = ((4096, 8192, 8192, 12288) if small else
             (D_MODEL * D_MODEL, D_MODEL * N_KV * HEAD_DIM,
              D_MODEL * N_KV * HEAD_DIM, D_MODEL * D_MODEL))
    Q = dq.DEFAULT_GROUP_QUANTUM
    n = sum(sizes)
    tg = jnp.asarray(np.repeat(np.arange(len(sizes)),
                               [s // Q for s in sizes]), jnp.int32)
    il_t = jnp.array([2, 3, 3, 2], jnp.int32)
    fl_t = jnp.array([6, 5, 5, 6], jnp.int32)
    fmt_tab = jnp.stack([il_t, fl_t], axis=1)
    x = jax.random.normal(next(keys), (n,)) * 0.5
    bits = jax.random.bits(next(keys), (n,), jnp.uint32)
    mask = jnp.ones((n,), jnp.float32)
    for mode in ("nearest", "stochastic"):
        w_k, m_k = dq.dps_quant_group_wire_pallas(
            x, fmt_tab, tg, jnp.zeros((1,), jnp.int32), bits, mask,
            stochastic=mode == "stochastic", quantum=Q, interpret=interpret)
        w_r, m_r = ref.dps_quant_group_wire_ref(x, il_t, fl_t, tg, bits,
                                                mask, Q, mode=mode)
        bad = int(jnp.sum(w_k != w_r))
        _check(bad == 0, f"group wire {mode}: {bad} elements differ")
        rel = stats_close(m_k, m_r, f"group wire {mode}")
        out.append(f"group/{mode} exact (stats {rel:.1e})")

    # fused decode-reduce over 4 ranks: exact (sums of 2^-FL multiples)
    chunk = n // 4
    wire = jax.random.randint(next(keys), (4, chunk), -128, 128,
                              jnp.int32).astype(jnp.int8)
    tg_c = tg[: chunk // Q]
    mean_k = dq.dps_wire_reduce_pallas(wire, fmt_tab, tg_c, quantum=Q,
                                       interpret=interpret)
    mean_r = ref.dps_wire_reduce_ref(wire, fl_t, tg_c, Q)
    bad = int(jnp.sum(mean_k != mean_r))
    _check(bad == 0, f"wire reduce: {bad} elements differ")
    out.append("reduce exact")

    # paged decode attention at decode widths: 8 slots, 16-token pages
    B, ps, P, n_pages = 8, 16, 37, 297
    H, KV, Dh = (4, 2, 16) if small else (N_HEADS, N_KV, HEAD_DIM)
    q = jax.random.normal(next(keys), (B, H, Dh), jnp.float32)
    pool = lambda k: jax.random.randint(k, (n_pages, ps, KV, Dh), -128, 128,
                                        jnp.int32).astype(jnp.int8)
    kp, vp = pool(next(keys)), pool(next(keys))
    fmt = jax.random.randint(next(keys), (n_pages, 2), 4, 9, jnp.int32)
    ptab = jax.random.randint(next(keys), (B, P), 0, n_pages, jnp.int32)
    lens = jnp.array([0, 1, 15, 16, 17, 200, 511, P * ps], jnp.int32)
    scale = Dh ** -0.5
    o_k = paged_attn_pallas(q, kp, vp, fmt, ptab, lens, scale=scale,
                            interpret=interpret)
    with jax.default_matmul_precision("highest"):
        o_r = jax.jit(lambda *a: _paged_attn_jnp(*a, scale=scale))(
            q, kp, vp, fmt, ptab, lens)
    o_k, o_r = np.asarray(o_k), np.asarray(o_r)
    _check(np.all(np.isfinite(o_k)), "paged attention: non-finite output")
    _check(np.all(o_k[0] == 0.0), "paged attention: empty row not zero")
    rel = float(np.max(np.abs(o_k - o_r)) / np.max(np.abs(o_r)))
    _check(rel <= ATTN_RTOL, f"paged attention: rel diff {rel:.2e}")
    out.append(f"paged_attn rel {rel:.1e}")
    return "; ".join(out)


def tree_quant_check(small: bool, key):
    """``quantize_tree`` with the fused leaf kernel (on a chip: the on-chip
    PRNG) against the jnp path, at the leaf shapes of the benchmark's
    ``internvl2-26b-l1`` (bf16 weights, vocab padded to 11776; the norms
    are left out by the policy).  ``small`` cuts every width by 32."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core import fixed_point as fxp
    from repro.core.policy import QuantPolicy
    from repro.kernels import ops

    d, ff, kv, vocab = (6144, 16384, 1024, 11776)
    if small:
        d, ff, kv, vocab = d // 32, ff // 32, kv // 32, vocab // 32
    shapes = {"embed/tok": (vocab, d), "embed/unembed": (d, vocab),
              "attn/wk": (1, d, kv), "attn/wo": (1, d, d),
              "attn/wq": (1, d, d), "attn/wv": (1, d, kv),
              "mlp/w_gate": (1, d, ff), "mlp/w_in": (1, d, ff),
              "mlp/w_out": (1, ff, d), "norm1": (1, d)}
    ks = jax.random.split(key, len(shapes) + 1)
    tree = {name: (jax.random.normal(k, s) * 0.02).astype(
                jnp.float32 if name.startswith("norm") else jnp.bfloat16)
            for k, (name, s) in zip(ks, shapes.items())}
    il, fl = 2, 12
    fmt = fxp.FixedPointFormat.create(il, fl)
    step = 2.0 ** -fl
    lo, hi = -(2.0 ** (il - 1)), 2.0 ** (il - 1) - step
    pred = QuantPolicy().param_predicate()

    def snap(t, quantize_fn):
        return fxp.quantize_tree(t, fmt, key=ks[-1], predicate=pred,
                                 quantize_fn=quantize_fn)

    q_k, s_k = jax.jit(functools.partial(
        snap, quantize_fn=ops.dps_quantize_leaf))(tree)
    _, s_j = jax.jit(functools.partial(snap, quantize_fn=fxp.quantize))(tree)
    weights = [n for n in shapes if n != "norm1"]
    _check(float(s_k.count) == sum(tree[n].size for n in weights)
           and bool(jnp.array_equal(q_k["norm1"], tree["norm1"])),
           "tree quant: the policy's leaves were not the ones snapped")
    err = sum(float(jnp.sum(q_k[n].astype(jnp.float32)
                            - jnp.clip(tree[n].astype(jnp.float32), lo, hi)))
              for n in weights) / (float(s_k.count) * step)
    _check(abs(err) <= PRNG_MEAN_ERR_STEPS,
           f"tree quant: mean rounding error {err:.2e} steps")
    for f in ("count", "nonzero", "overflow", "max_abs"):
        a, b = float(getattr(s_k, f)), float(getattr(s_j, f))
        _check(a == b, f"tree quant stat {f}: {a} vs {b}")
    rel = abs(float(s_k.abs_err_sum) / float(s_j.abs_err_sum) - 1.0)
    _check(rel <= PRNG_ABS_ERR_RTOL,
           f"tree quant: abs-err sum differs by {rel:.2e}")
    return (f"tree/{'bits' if small else 'prng'} mean err {err:+.1e} steps "
            f"over {int(s_k.count)} weights")


def ssd_scan_check(small: bool):
    """``models/ssm.py`` ``ssd_scan`` against the plain reference's ``ssd``
    at the benchmark's ``mamba2-1.3b-l16`` per-layer shapes (4 rows of
    2048, 64 heads of 64, state 128, chunk 256), with every chunk's summed
    decay far past 88.7, where exp of a positive segment sum overflows:
    the values and the gradients with respect to x, B, C and dt are finite
    and agree.  It runs the scan both ways: as ``ssd_scan`` chooses (on a
    TPU, the intra-chunk kernels of ``kernels/ssd.py``) and with the jnp
    form; the chosen path's gaps may pass the jnp form's by ``SSD_SLACK``.
    ``small`` keeps the chunk and cuts the rest."""
    import dataclasses
    from unittest import mock

    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench.reference import mamba2 as ref_ssm
    from repro.configs.base import get_config
    from repro.models import ssm

    B, S, H, P, N, Q = (1, 512, 4, 8, 16, 256) if small else \
        (4, 2048, 64, 64, 128, 256)
    cfg = dataclasses.replace(get_config("mamba2_1_3b"), ssm_chunk=Q)
    kx, kb, kc, kd, kw = jax.random.split(jax.random.key(15), 5)
    bf16 = lambda k, s: jax.random.normal(k, s).astype(jnp.bfloat16)
    args = (bf16(kx, (B, S, H, P)), bf16(kb, (B, S, N)),
            bf16(kc, (B, S, N)),
            jax.nn.softplus(jax.random.normal(kd, (B, S, H))))
    a = -jnp.ones((H,))
    w = jax.random.normal(kw, (B, S, H, P))
    decay = float(jnp.min(jnp.sum(args[3].reshape(B, S // Q, Q, H), axis=2)))
    kernel = ssm.intra_kernel_fits(Q, H, P)

    def program(x, b, c, dt):
        return ssm.ssd_scan(cfg, x, b, c, dt, dt * a)[0].astype(jnp.float32)

    def reference(x, b, c, dt):
        mm = lambda s, *ops: jnp.einsum(s, *ops,
                                        precision=jax.lax.Precision.HIGHEST)
        f32 = lambda t: t.astype(jnp.float32)
        return ref_ssm.ssd(f32(x), dt, a, f32(b), f32(c), Q, mm)

    def value_and_grads(f):
        def run(w, *xs):
            y, vjp = jax.vjp(f, *xs)
            return (y, *vjp(w))
        return [np.asarray(t, np.float32) for t in jax.jit(run)(w, *args)]

    names = ("y", "dy/dx", "dy/dB", "dy/dC", "dy/ddt")
    want = value_and_grads(reference)
    with mock.patch.object(ssm, "intra_kernel_fits", lambda *_: False):
        jnp_form = value_and_grads(program)
    chosen = value_and_grads(program) if kernel else jnp_form
    gaps = []
    for name, got, plain, ref in zip(names, chosen, jnp_form, want):
        _check(np.all(np.isfinite(got)), f"ssd scan: non-finite {name}")
        gap, plain_gap = (float(np.max(np.abs(t - ref)) / np.max(np.abs(ref)))
                          for t in (got, plain))
        _check(gap <= SSD_RTOL, f"ssd scan: {name} rel gap {gap:.2e}")
        _check(gap <= plain_gap + SSD_SLACK, f"ssd scan: {name} rel gap "
               f"{gap:.3e}, jnp form {plain_gap:.3e}")
        gaps.append(f"{name} rel {gap:.2e} (jnp {plain_gap:.2e})")
    return (f"ssd scan, {'kernels' if kernel else 'jnp'}, least chunk decay "
            f"{decay:.0f}: " + ", ".join(gaps))


# ---------------------------------------------------------------------------
# train and serve through their CLIs
# ---------------------------------------------------------------------------

def _depth_args(small: bool, layers: int = LAYERS):
    return ["--arch", ARCH] + (["--smoke"] if small else
                               ["--layers", str(layers)])


def train_phase(small: bool):
    from repro.launch import train

    seq = "64" if small else "2048"
    hist = train.main(_depth_args(small) + [
        "--batch", "2", "--seq", seq, "--steps", "8", "--log-every", "1"])
    losses = [h["loss"] for h in hist]
    _check(len(losses) == 8, f"{len(losses)} steps logged")
    _check(all(math.isfinite(l) for l in losses), f"losses {losses}")
    # at smoke widths the logits start near uniform, and eight steps move
    # the loss less than its batch-to-batch noise
    _check(small or losses[-1] < losses[0], f"loss did not fall: {losses}")
    return f"8 steps, loss {losses[0]:.4f} -> {losses[-1]:.4f}"


def serve_phase(small: bool):
    from repro.launch import serve

    n_req = 4 if small else 16
    shape = (["--slots", "2", "--page-size", "128", "--min-prompt", "16",
              "--max-prompt", "128", "--min-new", "4", "--max-new", "8"]
             if small else
             ["--slots", "8", "--page-size", "16", "--min-prompt", "128",
              "--max-prompt", "512", "--min-new", "32", "--max-new", "64"])
    tee = _Tee(sys.stdout)
    with contextlib.redirect_stdout(tee):
        report = serve.main(_depth_args(small) + [
            "--requests", str(n_req), "--attn-backend", "kernel",
            "--encode-backend", "kernel"] + shape)
    _check("attn=kernel, encode=kernel" in tee.text(),
           "the log does not show attn=kernel, encode=kernel")
    done = int(report.metrics["completed"])
    _check(done == n_req, f"{done}/{n_req} requests completed")
    vocab = 256 if small else 128256
    toks = [t for seq in report.tokens.values() for t in seq]
    _check(all(0 <= t < vocab for t in toks), "token id out of range")
    return (f"{done}/{n_req} requests, {len(toks)} tokens, "
            f"attn=kernel, encode=kernel")


def four_chip_phase(small: bool):
    """Data-parallel training over all four chips, int8 wire vs fp32."""
    import jax

    from repro.launch import train
    from repro.launch.hlo_stats import collective_wire_bytes

    n = jax.device_count()
    _check(n == 4, f"{n} devices, need 4")
    seq = "64" if small else "2048"
    args = _depth_args(small, LAYERS_4CHIP) + [
        "--batch", str(2 * n), "--seq", seq, "--steps", "6",
        "--log-every", "1"]
    runs = {}
    for name, extra in (("fp32", []), ("int8", ["--grad-allreduce-bits",
                                                 "8"])):
        shutil.rmtree(HLO_DUMP, ignore_errors=True)
        tee = _Tee(sys.stdout)
        with contextlib.redirect_stdout(tee):
            hist = train.main(args + extra)
        dumps = sorted(glob.glob(os.path.join(
            HLO_DUMP, "*jit_train_step*after_optimizations.txt")))
        _check(dumps, f"{name}: no compiled train step was dumped")
        with open(dumps[-1]) as f:
            runs[name] = ([h["loss"] for h in hist], tee.text(), f.read())
        shutil.rmtree(HLO_DUMP)  # the dump runs to hundreds of MB
        gc.collect()            # free this run's train state before the next

    l32, _, _ = runs["fp32"]
    l8, log8, hlo8 = runs["int8"]
    _check("wire_sync_active=True" in log8, "int8 wire did not engage")
    wire = collective_wire_bytes(hlo8)["by_op_dtype"]
    for op in ("all-to-all", "all-gather"):
        _check(wire.get(op, {}).get("s8", 0) > 0, f"no s8 {op}: {wire}")
    _check("num_partitions=4" in hlo8, "the step is not partitioned 4 ways")
    per_dev = f"s32[2,{int(seq) + 1}]"
    _check(per_dev in hlo8, f"no per-device batch shard {per_dev}")
    for d in jax.devices():
        stats = d.memory_stats() or {}
        _check(stats.get("peak_bytes_in_use", 0) > 1e9 or small,
               f"device {d.id} held no replica: {stats}")
    _check(all(math.isfinite(l) for l in l8 + l32), f"{l32} {l8}")
    _check(l8[-1] < l8[0], f"int8 loss did not fall: {l8}")
    worst = max(abs(a - b) / abs(b) for a, b in zip(l8, l32))
    _check(worst <= WIRE_LOSS_RTOL,
           f"loss curves differ by {worst:.2e} > {WIRE_LOSS_RTOL}")
    s8 = {op: int(wire[op]["s8"]) for op in ("all-to-all", "all-gather")}
    return (f"wire_sync_active=True; s8 wire bytes {s8}; "
            f"fp32 loss {[round(l, 4) for l in l32]}; "
            f"int8 loss {[round(l, 4) for l in l8]}; "
            f"max rel diff {worst:.2e}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="run on the CPU at smoke widths (never a chip run)")
    args = ap.parse_args(argv)

    if args.chips == 4:
        # keep the compiled train steps, to read their collectives
        flags = [f"--xla_dump_to={HLO_DUMP}",
                 "--xla_dump_hlo_module_re=jit_train_step"]
        if args.rehearse:
            flags.append("--xla_force_host_platform_device_count=4")
        os.environ["XLA_FLAGS"] = " ".join(
            [os.environ.get("XLA_FLAGS", "")] + flags).strip()

    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {json.dumps(device)}", flush=True)
    if not args.rehearse and device["platform"] != "tpu":
        print("no TPU: JAX found only "
              f"{device['platform']} devices", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.device import enable_compile_cache

    enable_compile_cache()
    if args.chips == 4:
        # a step loaded from the cache is never dumped; the CLIs point the
        # cache at its directory but leave this switch alone
        jax.config.update("jax_enable_compilation_cache", False)

    small = args.rehearse
    results = {}
    if args.chips == 4:
        _run_phase("four_chip_train", lambda: four_chip_phase(small), results)
    else:
        _run_phase("kernels", lambda: kernels_phase(small), results)
        _run_phase("ssd_scan", lambda: ssd_scan_check(small), results)
        _run_phase("train", lambda: train_phase(small), results)
        _run_phase("serve", lambda: serve_phase(small), results)

    ok = all(results.values())
    if not ok:
        print(f"failed phases: {[k for k, v in results.items() if not v]}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
