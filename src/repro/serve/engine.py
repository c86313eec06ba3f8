"""Continuous-batching inference engine over the paged DPS KV cache.

Prefill/decode split: each admission runs the prompt once at batch 1
(compiled at the layout's fixed ``max_prompt``), encodes the resulting
contiguous fp32 cache into int8 pages (``cache.write_prompt_pages``), and
drops the request into a free decode row.  Decode is one jointly-batched
compiled step over all ``batch_slots`` rows — inactive rows ride along
pointed at the trash page — so admissions and retirements only rewrite
*inputs* (page table, positions, last tokens) and never recompile.

Exactly three compiled shapes exist for a layout: prefill, encode, decode.
"""

from __future__ import annotations

import dataclasses
import time
from collections import Counter
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import registry
from repro.models.common import init_params, unembed
from repro.device import on_tpu
from repro.serve import cache as kvc
from repro.serve.page_table import PageAllocator, PagedLayout, page_rows
from repro.serve.scheduler import Request, Scheduler


def supports_paging(cfg: ModelConfig) -> bool:
    """Paged serving needs the GQA decode path (no MLA latent cache, no
    SSM state, no encoder context)."""
    return cfg.family in ("dense", "moe") and not cfg.mla


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    layout: PagedLayout
    kv_bits: Optional[int] = 8     # 8 = int8 DPS pages; None = fp32 pages
    attn_backend: str = "auto"     # fused decode attention: kernel | jnp
    encode_backend: str = "auto"   # page codec: kernel | jnp
    il_init: int = kvc.DEFAULT_IL_INIT
    max_concurrency: Optional[int] = None  # 1 = serial-serving baseline


@dataclasses.dataclass
class ServeReport:
    tokens: Dict[int, List[int]]   # rid -> generated token ids (greedy)
    metrics: Dict[str, float]
    format_spread: Dict[str, int]  # "<il,fl>" -> live prompt pages placed


class Engine:
    """Holds the compiled step functions; :meth:`run` drives a trace."""

    def __init__(self, cfg: ModelConfig, params, ecfg: EngineConfig):
        if not supports_paging(cfg):
            raise ValueError(f"{cfg.name}: family {cfg.family!r} (mla="
                             f"{cfg.mla}) has no paged decode path")
        if ecfg.kv_bits not in (None, 8):
            raise ValueError(f"kv_bits must be 8 or None, got {ecfg.kv_bits}")
        # the engine owns KV quantization at page granularity; the model's
        # own contiguous int8-cache mode must not double-quantize prefill
        if cfg.kv_cache_bits == 8:
            cfg = dataclasses.replace(cfg, kv_cache_bits=16)
        self.cfg = cfg
        self.ecfg = ecfg
        self.layout = ecfg.layout
        self.bits = ecfg.kv_bits
        self.params = params
        self.mod = registry(cfg.family)

        lay = self.layout
        page_elems = lay.page_size * cfg.n_kv_heads * cfg.head_dim
        # "auto" is the kernel on a TPU and jnp elsewhere; a page the
        # grouped kernel cannot tile raises rather than falling back
        auto = "kernel" if on_tpu() else "jnp"
        self._attn_backend = (ecfg.attn_backend if ecfg.attn_backend != "auto"
                              else auto)
        eb = ecfg.encode_backend if ecfg.encode_backend != "auto" else auto
        if eb == "kernel" and page_elems % 4096:
            raise ValueError(
                f"page holds {page_elems} elements — the grouped wire "
                f"kernel needs a multiple of 4096; use encode_backend='jnp' "
                f"or a larger page")
        self._enc_backend = eb

        self.plan = (kvc.kv_plan(cfg, lay, ecfg.il_init)
                     if self.bits == 8 else None)

        def prefill_impl(params, tokens, plen):
            hidden, cache2, _, _ = self.mod.forward(
                cfg, params, tokens, mode="prefill", hidden_only=True)
            last = jax.lax.dynamic_index_in_dim(hidden, plen - 1, axis=1)
            logits = unembed(last, params["embed"], cfg.vocab)
            return logits[0, -1].astype(jnp.float32), cache2[0], cache2[1]

        def encode_impl(pools, state, ck, cv, phys, plen):
            return kvc.write_prompt_pages(
                cfg, lay, self.plan, pools, state, ck, cv, phys, plen,
                bits=self.bits, encode_backend=self._enc_backend)

        self._prefill = jax.jit(prefill_impl)
        self._encode = jax.jit(encode_impl)
        self._decode = jax.jit(self.decode_impl)
        if self.bits == 8:
            self._reset = jax.jit(
                lambda state, mask: kvc.reset_rows(self.plan, state, mask))

    def decode_impl(self, params, tokens, pools, state, ptab, pos):
        """One batched decode step (also the analysis entry point).

        ``state`` is the kv_cache FlexState at ``kv_bits=8`` and ``None``
        at ``kv_bits=None`` (fp32 pages, zero-FL tables → ×1.0 dequant).
        """
        if self.bits == 8:
            k_fmt, v_fmt = kvc.fmt_tables(state, self.cfg, self.layout)
        else:
            k_fmt, v_fmt = kvc.zero_fmt_tables(self.cfg, self.layout)
        cache = (pools.k_pages, pools.v_pages, k_fmt, v_fmt)
        logits, new_cache = self.mod.decode_step_paged(
            self.cfg, params, tokens, cache, ptab, pos,
            backend=self._attn_backend)
        return (logits.astype(jnp.float32),
                kvc.PagedKV(new_cache[0], new_cache[1]))

    # ------------------------------------------------------------------
    # the serving loop
    # ------------------------------------------------------------------

    def run(self, requests: Sequence[Request], *,
            max_steps: Optional[int] = None) -> ServeReport:
        if self.params is None:
            raise ValueError("engine built without params (analysis-only)")
        lay, B = self.layout, self.layout.batch_slots
        for r in requests:
            need = lay.pages_needed(r.prompt.size, r.max_new)
            if not lay.fits(r.prompt.size, r.max_new) or need > lay.n_pages:
                raise ValueError(
                    f"request {r.rid} (prompt {r.prompt.size}, max_new "
                    f"{r.max_new} -> {need} pages) can never fit layout "
                    f"{lay}")

        sched = Scheduler(requests)
        alloc = PageAllocator(lay.n_pages)
        pools = kvc.init_pool(self.cfg, lay, self.bits)
        state = self.plan.init()[kvc.KV_DOMAIN] if self.bits == 8 else None

        ptab = np.full((B, lay.max_pages_per_seq), lay.trash_page, np.int32)
        pos = np.zeros(B, np.int32)
        last = np.zeros(B, np.int32)
        slots: List[Optional[dict]] = [None] * B
        tokens_out: Dict[int, List[int]] = {r.rid: [] for r in requests}
        lat: List[float] = []
        prefill_s: List[float] = []
        occ: List[int] = []
        spread: Counter = Counter()
        cap = min(self.ecfg.max_concurrency or B, B)
        guard = max_steps if max_steps is not None else (
            sum(r.max_new for r in requests)
            + max((r.arrival for r in requests), default=0)
            + len(requests) + 16)

        L, n_tot = self.cfg.n_layers, lay.n_pages_total
        step = 0
        bp_steps = 0   # steps an arrived request was held for page frees
        t0 = time.perf_counter()
        while sched.pending or any(s is not None for s in slots):
            if step > guard:
                raise RuntimeError(f"serving loop exceeded {guard} steps")

            # retire finished rows: free pages, clear precision history
            for b, s in enumerate(slots):
                if s is not None and s["produced"] >= s["req"].max_new:
                    alloc.release(s["pages"])
                    if self.bits == 8:
                        rows = page_rows(L, n_tot, s["pages"]).reshape(-1)
                        mask = np.zeros(kvc.n_rows(self.cfg, lay), bool)
                        mask[rows] = True
                        state = self._reset(state, jnp.asarray(mask))
                    ptab[b] = lay.trash_page
                    pos[b] = 0
                    last[b] = 0
                    slots[b] = None

            # admit (strict FCFS) while a slot is free and pages cover the
            # head request's whole lifetime
            while sum(s is not None for s in slots) < cap:
                req = sched.pop_admissible(
                    step, lambda r: alloc.can(
                        lay.pages_needed(r.prompt.size, r.max_new)))
                if req is None:
                    # head arrived but can't start -> pool backpressure:
                    # the request waits in the queue for frees, it is
                    # never dropped
                    if (sched.pending
                            and sched.pending[0].arrival <= step):
                        bp_steps += 1
                    break
                b = next(i for i, s in enumerate(slots) if s is None)
                try:
                    pools, state = self._admit(
                        b, req, alloc, pools, state, ptab, pos, last,
                        slots, tokens_out, prefill_s, spread)
                except RuntimeError:
                    # allocator exhaustion despite the can() pre-check
                    # (accounting drift): hold the request at the queue
                    # head and retry after the next retire frees pages —
                    # backpressure, not a crash
                    sched.requeue(req)
                    bp_steps += 1
                    break

            act = [b for b, s in enumerate(slots) if s is not None]
            if act:
                occ.append(len(act))
                t_d = time.perf_counter()
                logits, pools = self._decode_call(pools, state, ptab, pos,
                                                  last)
                nxt = np.asarray(jnp.argmax(logits, axis=-1), np.int32)
                dt = time.perf_counter() - t_d
                for b in act:
                    s = slots[b]
                    tokens_out[s["req"].rid].append(int(nxt[b]))
                    s["produced"] += 1
                    pos[b] += 1
                    last[b] = nxt[b]
                    lat.append(dt)
            elif sched.pending:
                nxt_arr = sched.next_arrival()
                if nxt_arr is not None and nxt_arr > step + 1:
                    step = nxt_arr - 1          # fast-forward idle gaps
            step += 1

        wall = time.perf_counter() - t0
        total = sum(len(v) for v in tokens_out.values())
        metrics = {
            "completed": float(sum(len(tokens_out[r.rid]) == r.max_new
                                   for r in requests)),
            "wall_s": wall,
            "total_tokens": float(total),
            "tokens_per_s": total / wall if wall > 0 else 0.0,
            "decode_steps": float(len(occ)),
            "decoded_tokens": float(len(lat)),
            "p50_ms_per_token": float(np.percentile(lat, 50) * 1e3)
            if lat else 0.0,
            "p95_ms_per_token": float(np.percentile(lat, 95) * 1e3)
            if lat else 0.0,
            "mean_occupancy": float(np.mean(occ)) if occ else 0.0,
            "prefill_s_total": float(np.sum(prefill_s)) if prefill_s else 0.0,
            "backpressure_steps": float(bp_steps),
        }
        return ServeReport(tokens_out, metrics, dict(spread))

    def _admit(self, b, req, alloc, pools, state, ptab, pos, last, slots,
               tokens_out, prefill_s, spread):
        lay = self.layout
        plen = int(req.prompt.size)
        need = lay.pages_needed(plen, req.max_new)
        pages = alloc.alloc(need)

        t_a = time.perf_counter()
        toks = np.zeros(lay.max_prompt, np.int32)
        toks[:plen] = req.prompt
        logits, ck, cv = self._prefill(self.params, jnp.asarray(toks)[None],
                                       jnp.int32(plen))
        phys = np.full(lay.prompt_pages, lay.trash_page, np.int32)
        npp = min(need, lay.prompt_pages)
        phys[:npp] = pages[:npp]
        pools, state = self._encode(pools, state, ck, cv, jnp.asarray(phys),
                                    jnp.int32(plen))
        first = int(jnp.argmax(logits))
        prefill_s.append(time.perf_counter() - t_a)

        row = np.full(lay.max_pages_per_seq, lay.trash_page, np.int32)
        row[:need] = pages
        ptab[b] = row
        pos[b] = plen
        last[b] = first
        slots[b] = {"req": req, "pages": pages, "produced": 1}
        tokens_out[req.rid].append(first)

        if self.bits == 8:
            live = -(-plen // lay.page_size)
            rows = page_rows(self.cfg.n_layers, lay.n_pages_total,
                             pages[:live]).reshape(-1)
            il = np.asarray(state.il)[rows]
            fl = np.asarray(state.fl)[rows]
            spread.update(f"<{int(a)},{int(f)}>" for a, f in zip(il, fl))
        return pools, state

    def _decode_call(self, pools, state, ptab, pos, last):
        toks = jnp.asarray(last[:, None])
        return self._decode(self.params, toks, pools, state,
                            jnp.asarray(ptab), jnp.asarray(pos))


def analysis_decode(cfg: ModelConfig, ecfg: EngineConfig):
    """(fn, abstract_args) for the verifier/HLO audit — no weights touched.

    ``fn`` is the un-jitted decode step; ``abstract_args`` are
    ShapeDtypeStructs at the layout's production shapes, so
    ``jax.make_jaxpr(fn)(*args)`` / ``jax.jit(fn).lower(*args)`` cost no
    pool memory.
    """
    eng = Engine(cfg, None, ecfg)
    lay = ecfg.layout
    defs = eng.mod.model_defs(eng.cfg)
    params = jax.eval_shape(lambda k: init_params(k, defs),
                            jax.random.key(0))
    pools = jax.eval_shape(lambda: kvc.init_pool(eng.cfg, lay, eng.bits))
    state = (jax.eval_shape(lambda: eng.plan.init()[kvc.KV_DOMAIN])
             if eng.bits == 8 else None)
    B = lay.batch_slots
    i32 = jnp.int32
    abstract_args = (
        params,
        jax.ShapeDtypeStruct((B, 1), i32),
        pools,
        state,
        jax.ShapeDtypeStruct((B, lay.max_pages_per_seq), i32),
        jax.ShapeDtypeStruct((B,), i32),
    )
    return eng.decode_impl, abstract_args
