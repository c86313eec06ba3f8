"""Precision-flow lint: run all three analysis passes over a config grid.

    PYTHONPATH=src python -m repro.analysis.lint                  # full grid
    PYTHONPATH=src python -m repro.analysis.lint --config lenet --zero-opt
    PYTHONPATH=src python -m repro.analysis.lint --config llama3_2_3b \
        --wire-groups per-layer

Each cell builds a REAL train step (the same constructors the launch and
test code use), traces it, compiles it, and proves the wire invariants
three ways: jaxpr dataflow (:mod:`repro.analysis.flow`), compiled-HLO
byte audit (:mod:`repro.analysis.hlo_audit`), and static Pallas call-site
geometry (:mod:`repro.analysis.kernel_checks`).  Exits nonzero on any
violation.

The mesh is one pure data-parallel axis over every visible device
(``xla_force_host_platform_device_count=8`` in CI) — the topology where
the compressed wire paths actually engage, mirroring the dist test legs.
Arch configs (``--config llama3_2_3b``) compile with two probe-sized
layers and a short sequence: the wire schedule per step is
depth-independent (one collective pair regardless of leaf count), so the
shrunk cell proves the same invariants at a fraction of the compile cost.

The mode grid:

* ``baseline``       — no wire: flow rules must pass vacuously-clean.
* ``tree``           — global-format compressed gradient all-reduce
                       (``grad_allreduce_bits=8``, one tree collective
                       pair).
* ``per-layer``      — one wire ⟨IL, FL⟩ per param leaf (grouped tree +
                       group-aligned kernel schedule).
* ``zero``           — ZeRO-1: int8 reduce-scatter + parameter
                       all-gather over the plain flat layout.
* ``zero-per-layer`` — ZeRO-1 + per-layer wire formats: both sharded
                       halves run the grouped codec over the
                       group-aligned flat layout.
* ``zero-overlap``   — ZeRO-1 + the backward-overlapped bucketed wire:
                       one int8 reduce-scatter per bucket in backward
                       ready order over the bucketed aligned layout.
* ``serve-decode``   — the serving engine's paged decode step
                       (:mod:`repro.serve`): flow proves the kv_page
                       wire contract (PF-KV-WIRE), the HLO audit proves
                       the pool stays int8 with no materialized fp32
                       cache (HA-KV-DTYPE / HA-KV-F32-CACHE), and the
                       kernel pass checks the fused paged-attention and
                       page-encode launches at production dims.

``--wire-overlap on`` rebuilds the ``tree`` and ``per-layer`` cells with
the backward-overlapped bucketed wire (:mod:`repro.dist.overlap`) — the
flow pass then additionally proves PF-BUCKET-ENCODE / PF-BUCKET-DECODE
(every bucket encoded exactly once and decoded before the optimizer
consumes it); the same rules are proven on the sharded reduce-scatter
half by the ``zero-overlap`` cell, which carries the overlap intrinsically.
``baseline`` is unaffected.
"""

from __future__ import annotations

import argparse
import dataclasses
import sys
from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.analysis import flow, hlo_audit, kernel_checks
from repro.analysis.report import Report
from repro.core import qtrain
from repro.dist import collectives
from repro.dist.sharding import make_mesh

MODES = ("baseline", "tree", "per-layer", "zero", "zero-per-layer",
         "zero-overlap", "serve-decode")


def _data_mesh():
    n = len(jax.devices())
    return make_mesh((n,), ("data",))


def _mode_qcfg(mode: str, n_ranks: int, wire_controller: str,
               wire_overlap: bool = False,
               guards: bool = False) -> qtrain.QuantConfig:
    kw = dict(enabled=True, controller="paper",
              wire_controller=wire_controller)
    if mode in ("tree", "per-layer"):
        kw["grad_allreduce_bits"] = 8
        kw["wire_overlap"] = wire_overlap
    elif mode in ("zero", "zero-per-layer", "zero-overlap"):
        kw["grad_allreduce_bits"] = 8
        kw["zero_opt_shards"] = n_ranks
        kw["wire_overlap"] = mode == "zero-overlap"
    if guards:
        from repro.resilience import GuardConfig
        kw["guards"] = GuardConfig()
    return qtrain.QuantConfig(**kw)


def _claims(qcfg: qtrain.QuantConfig, mesh, params,
            n_params: int) -> hlo_audit.AuditClaims:
    engaged: List[str] = []
    two_leg = True
    declared_f32 = 0.0
    n_wire = n_params
    if qtrain.wire_sync_engaged(qcfg, mesh):
        engaged.append("wire_grads")
    if qtrain.zero_opt_engaged(qcfg, mesh):
        engaged.append("wire_grads")
        # both sharded legs ship the flat layout's padded element count —
        # under the group-aligned partitioner that exceeds the raw param
        # count (every leaf slot is padded to the wire quantum)
        part = qtrain.zero_partitioner(qcfg, params, qcfg.zero_opt_shards)
        n_wire = part.padded_size
        if qtrain.wire_params_engaged(qcfg, params, mesh):
            engaged.append("wire_params")
        else:
            # the policy excludes leaves: the param all-gather falls back
            # to fp32 BY DESIGN — one declared fp32 gather, one s8 leg
            two_leg = False
            declared_f32 = 4.0 * part.padded_size * 1.25
    if qcfg.guards is not None and engaged:
        # a guarded step compiles the fp32 fallback branch of every wire
        # cond ALONGSIDE the int8 branch (graceful degradation, see
        # repro.resilience + dist/README.md): those bytes are declared
        # capacity, not residual leakage.  Ring model: the non-ZeRO
        # fallback all-reduce counts 2x its payload; the ZeRO fallback
        # pair (reduce-scatter + all-gather) is 1x + 1x over the padded
        # flat layout — both are 2 x 4 B x n_wire (x1.25 padding fudge).
        declared_f32 += 2.0 * 4.0 * n_wire * 1.25
    # grouped (zero-f32-concat) is NOT claimed on the full step: model
    # activations legitimately concatenate in fp32.  The strict concat
    # claim runs on the isolated wire pipeline (_wire_pipeline_report).
    return hlo_audit.AuditClaims(
        engaged=tuple(dict.fromkeys(engaged)),
        two_leg=two_leg,
        grouped=False,
        f32_declared_bytes=declared_f32,
        n_wire_elems=n_wire if engaged else None)


def _kernel_reports(mode: str, leaf_sizes, n_ranks: int,
                    name: str) -> List[Report]:
    """Static geometry of the Pallas launches this cell WOULD run on the
    kernel backend (the TPU tiling is checkable anywhere)."""
    from repro.kernels import ops
    total = sum(leaf_sizes)
    if "per-layer" in mode:
        sizes, groups = tuple(leaf_sizes), len(leaf_sizes)
    else:
        sizes, groups = (total,), 1
    q = collectives.default_wire_quantum(total, groups, "kernel")
    layout = collectives.group_layout(sizes, n_chunks=n_ranks, quantum=q)
    return [
        kernel_checks.check_layout(layout, name=f"{name}/layout"),
        kernel_checks.check_call(
            ops.group_wire_call_geometry(layout.total, groups, q),
            expected_groups=groups, name=f"{name}/encode"),
        kernel_checks.check_call(
            ops.wire_reduce_call_geometry(n_ranks, layout.chunk, groups, q),
            expected_groups=groups, name=f"{name}/reduce"),
    ]


def _wire_pipeline_report(mode: str, leaf_sizes, mesh, name: str,
                          wire_overlap: bool = False) -> Report:
    """Audit the wire pipeline compiled in ISOLATION (the
    ``bench_collectives`` idiom): a shard_map'ed tree all-reduce over
    grad-shaped leaves.  Only here is the zero-f32-concatenate claim
    checkable — a full model step concatenates fp32 activations freely."""
    from jax.sharding import PartitionSpec as P
    from repro.core.fixed_point import FixedPointFormat

    per_layer = mode == "per-layer"
    groups = len(leaf_sizes) if per_layer else 1
    if per_layer:
        fmt = FixedPointFormat(jnp.full((groups,), 3, jnp.int32),
                               jnp.full((groups,), 5, jnp.int32))
    else:
        fmt = FixedPointFormat.create(3, 5)
    tree = {f"leaf{i}": jax.ShapeDtypeStruct((s,), jnp.float32)
            for i, s in enumerate(leaf_sizes)}
    key = jax.eval_shape(lambda: jax.random.key(1))

    def body(tr, k):
        if wire_overlap:
            from repro.dist import overlap as overlap_lib
            mean, _ = overlap_lib.bucketed_allreduce_mean_tree(
                tr, fmt, "data", k)
        else:
            mean, _ = collectives.dps_allreduce_mean_tree(tr, fmt, "data", k)
        return mean

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=({k: P() for k in tree}, P()), out_specs=P(),
        check_vma=False))
    hlo = fn.lower(tree, key).compile().as_text()
    claims = hlo_audit.AuditClaims(
        engaged=("wire_grads",), two_leg=True, grouped=True,
        f32_concat_budget=64.0 * groups,
        n_wire_elems=sum(leaf_sizes))
    return hlo_audit.audit_hlo(hlo, claims, name=name)


def _lenet_cell(mode: str, mesh, wire_controller: str,
                wire_overlap: bool = False,
                guards: bool = False) -> List[Report]:
    from repro.models import lenet
    from repro.optim import SGDConfig, make_optimizer

    n = mesh.devices.size
    qcfg = _mode_qcfg(mode, n, wire_controller, wire_overlap, guards)
    params = lenet.init(jax.random.key(0))
    if "per-layer" in mode:
        qcfg = qcfg.with_per_layer_wire(params)
    opt = make_optimizer(SGDConfig())
    # qcfg rides along so ZeRO cells init whichever flat layout the step
    # will use (group-aligned under per-layer wire / overlap)
    opt_state = (qtrain.zero_opt_state(opt, params, n, qcfg=qcfg)
                 if mode.startswith("zero") else opt.init(params))
    state = qtrain.TrainState.create(params, opt_state, qcfg,
                                     jax.random.key(1))
    batch = {"images": jnp.zeros((2 * n, 28, 28, 1), jnp.float32),
             "labels": jnp.zeros((2 * n,), jnp.int32)}
    step = qtrain.make_train_step(lenet.loss_fn, opt, qcfg, mesh=mesh)
    name = f"lenet/{mode}"
    leaf_sizes = [l.size for l in jax.tree.leaves(params)]
    return _step_reports(step, (state, batch), qcfg, mesh, mode,
                         params, leaf_sizes, name, wire_overlap)


def _arch_cell(arch: str, mode: str, mesh, wire_controller: str,
               seq: int, wire_overlap: bool = False,
               guards: bool = False) -> List[Report]:
    from repro.configs.base import ShapeConfig, get_config, smoke
    from repro.launch import specs as specs_lib
    from repro.optim import SGDConfig, make_optimizer

    # the wire schedule is depth/width-independent (one collective pair,
    # G = leaf count), so the smoke-sized config proves the same invariants
    cfg = dataclasses.replace(smoke(get_config(arch)), probe_unroll=True)

    n = mesh.devices.size
    shape = ShapeConfig("lint_train", "train", seq=seq, batch=n)
    qcfg = _mode_qcfg(mode, n, wire_controller, wire_overlap, guards)
    if "per-layer" in mode:
        qcfg = specs_lib.per_layer_wire_qcfg(cfg, qcfg)
    opt = make_optimizer(SGDConfig())
    step = specs_lib.build_train_step(cfg, qcfg, opt, mesh=mesh)
    astate = specs_lib.abstract_train_state(cfg, opt, qcfg, mesh=mesh)
    abatch = specs_lib.train_batch_specs(cfg, shape)
    name = f"{arch}/{mode}"
    leaf_sizes = [l.size for l in jax.tree.leaves(astate.params)]
    return _step_reports(step, (astate, abatch), qcfg, mesh, mode,
                         astate.params, leaf_sizes, name, wire_overlap)


def _step_reports(step, abstract_args, qcfg, mesh, mode: str, params,
                  leaf_sizes, name: str,
                  wire_overlap: bool = False) -> List[Report]:
    n_params = sum(leaf_sizes)
    reports = [flow.analyze_jaxpr(jax.make_jaxpr(step)(*abstract_args),
                                  name=f"{name}/flow")]
    claims = _claims(qcfg, mesh, params, n_params)
    hlo = jax.jit(step).lower(*abstract_args).compile().as_text()
    reports.append(hlo_audit.audit_hlo(hlo, claims, name=f"{name}/hlo"))
    if claims.engaged:
        if mode in ("tree", "per-layer"):
            reports.append(_wire_pipeline_report(mode, leaf_sizes, mesh,
                                                 f"{name}/pipeline",
                                                 wire_overlap))
        reports.extend(_kernel_reports(mode, leaf_sizes, mesh.devices.size,
                                       f"{name}/kernel"))
    return reports


def _serve_cell(config: str) -> List[Report]:
    """The serving decode step: flow + HLO at smoke scale (the wire
    contract is size-independent), kernel geometry at production dims
    (the TPU tiling is what production would launch)."""
    from repro.configs.base import get_config, smoke
    from repro.kernels import ops
    from repro.serve import EngineConfig, PagedLayout, analysis_decode

    arch = "llama3_2_3b" if config == "lenet" else config
    cfg = smoke(get_config(arch))
    # pool sized so one stacked page pool out-counts every legit f32
    # tensor in the smoke step (the 32k-element embed table is largest) —
    # the F32-CACHE threshold then cleanly separates a dequantized pool
    # from model weights
    lay = PagedLayout(page_size=4, n_pages=192, batch_slots=4,
                      max_pages_per_seq=8, max_prompt=16)
    ecfg = EngineConfig(layout=lay, kv_bits=8, attn_backend="jnp",
                        encode_backend="jnp")
    fn, args = analysis_decode(cfg, ecfg)
    name = f"{arch}/serve-decode"

    flow_rep = flow.analyze_jaxpr(jax.make_jaxpr(fn)(*args),
                                  name=f"{name}/flow")
    if "PF-KV-WIRE" not in flow_rep.checked:
        flow_rep.add("PF-KV-WIRE",
                     "decode step never tags its KV pages (kv_page "
                     "landmarks absent) — the page wire contract is "
                     "unverifiable", name)
    reports = [flow_rep]

    pool_elems = (cfg.n_layers * lay.n_pages_total * lay.page_size
                  * cfg.n_kv_heads * cfg.head_dim)
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    reports.append(hlo_audit.audit_decode_hlo(
        hlo, pool_elems=pool_elems, bits=8, name=f"{name}/hlo"))

    prod = get_config(arch)
    B, P, ps, n_pages = 8, 16, 128, 512
    page_elems = ps * prod.n_kv_heads * prod.head_dim
    reports.append(kernel_checks.check_call(
        ops.paged_attn_call_geometry(B, P, n_pages + 1, ps,
                                     prod.n_kv_heads, prod.head_dim),
        expected_groups=n_pages + 1, name=f"{name}/attn-kernel"))
    groups = 2 * prod.n_layers * (P // 2)   # one admission's page encode
    reports.append(kernel_checks.check_call(
        ops.group_wire_call_geometry(groups * page_elems, groups,
                                     page_elems),
        expected_groups=groups, name=f"{name}/encode-kernel"))
    return reports


def lint_cell(config: str, mode: str, mesh=None,
              wire_controller: str = "flexpoint",
              seq: int = 128, wire_overlap: bool = False,
              guards: bool = False) -> List[Report]:
    """All three passes over one (config, mode) cell; returns Reports."""
    if mode == "serve-decode":
        return _serve_cell(config)
    mesh = mesh or _data_mesh()
    if config == "lenet":
        return _lenet_cell(mode, mesh, wire_controller, wire_overlap, guards)
    return _arch_cell(config, mode, mesh, wire_controller, seq, wire_overlap,
                      guards)


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="Statically verify the wire invariants of compiled "
                    "steps (see src/repro/analysis/README.md).")
    ap.add_argument("--config", action="append", default=None,
                    help="config to lint: 'lenet' (default) or an arch "
                         "name from repro.configs.base (repeatable)")
    ap.add_argument("--zero-opt", action="store_true",
                    help="lint only the ZeRO-1 cell (composes with "
                         "--wire-groups per-layer / --wire-overlap on to "
                         "select the group-aligned cells)")
    ap.add_argument("--wire-groups", choices=("global", "per-layer"),
                    default=None,
                    help="lint only the tree (global) or per-layer cell")
    ap.add_argument("--modes", default=None,
                    help=f"comma-separated subset of {MODES}")
    ap.add_argument("--wire-controller", default="flexpoint")
    ap.add_argument("--wire-overlap", choices=("on", "off"), default="off",
                    help="rebuild the tree/per-layer cells with the "
                         "backward-overlapped bucketed wire (the "
                         "zero-overlap cell carries it intrinsically; "
                         "combined with --zero-opt this selects that cell)")
    ap.add_argument("--guards", action="store_true",
                    help="arm the repro.resilience health guards in every "
                         "train cell: the flow pass then proves "
                         "PF-GUARD-TAINT (degradation signals descend "
                         "from wire-leg stats) and the HLO audit admits "
                         "the compiled fp32 fallback branches as declared "
                         "bytes under HA-F32-RESIDUAL")
    ap.add_argument("--seq", type=int, default=128,
                    help="sequence length for arch train cells")
    args = ap.parse_args(argv)

    if args.zero_opt:
        if args.wire_groups == "per-layer":
            modes = ["zero-per-layer"]
        elif args.wire_overlap == "on":
            modes = ["zero-overlap"]
        else:
            modes = ["zero"]
    elif args.wire_groups is not None:
        modes = ["per-layer" if args.wire_groups == "per-layer" else "tree"]
    elif args.modes:
        modes = [m.strip() for m in args.modes.split(",")]
    else:
        modes = list(MODES)
    for m in modes:
        if m not in MODES:
            ap.error(f"unknown mode {m!r} (choose from {MODES})")
    wire_overlap = args.wire_overlap == "on"
    configs = args.config or ["lenet"]

    mesh = _data_mesh()
    print(f"precision-flow lint: {len(jax.devices())} device(s), "
          f"configs={configs}, modes={modes}", flush=True)
    n_viol = 0
    for config in configs:
        for mode in modes:
            try:
                reports = lint_cell(config, mode, mesh,
                                    args.wire_controller, args.seq,
                                    wire_overlap, args.guards)
            except Exception as e:          # a cell that cannot build IS a
                n_viol += 1                 # lint failure, not a skip
                print(f"ERROR {config}/{mode}: {e!r}", flush=True)
                continue
            for r in reports:
                print(f"  {r.summary()}", flush=True)
                n_viol += len(r.violations)
    print(f"precision-flow lint: "
          f"{'CLEAN' if not n_viol else f'{n_viol} violation(s)'}",
          flush=True)
    return 1 if n_viol else 0


if __name__ == "__main__":
    sys.exit(main())
