"""Gradient all-reduce wire benchmark: fp32 vs the int8 DPS codec (§dist).

Compares ways to average a gradient-sized tensor across a host-device
data mesh:

  * ``fp32``    — ``lax.pmean``: XLA's stock all-reduce,
  * ``int8_jnp``    — ``dps_allreduce_mean`` with the jnp wire codec,
  * ``int8_kernel`` — the same collective with the fused Pallas
    ``dps_quant_wire`` codec (interpret mode on CPU — numerics-identical,
    walltime is emulation cost only; honest kernel timing needs a TPU),
  * ``int8_jnp_grouped`` / ``int8_kernel_grouped`` — per-group ⟨IL, FL⟩
    (a [G] format table, one row per layer-sized group) through BOTH legs
    via the group-aligned layout; the kernel variant runs the [G, 2]
    SMEM-table grouped encode + the fused ``dps_wire_reduce`` receive.

Reported per variant: ring-model wire bytes parsed from the compiled HLO
(see ``repro.launch.hlo_stats``), walltime per step, and an **HBM-traffic
model** column (modeled bytes each rank moves through HBM per collective,
separating the fused one-pass pipeline from the naive multi-pass path).
Headline claims: the int8 two-leg path moves ≤ ~1/4 the wire bytes of the
fp32 all-reduce, the grouped-kernel path stays within 1.35× of the
global-format kernel walltime (interpret-mode emulation cost is host-
dependent — the bound guards against the [G, 2]-table machinery grossly
blowing up the kernel, not against per-host constant factors), and the rebuilt tree all-reduce compiles
with NO fp32 flat-concatenate (verified via ``hlo_stats.concat_bytes``).

Two more sections feed the ``overlap_*`` keys of the repo-root
``BENCH_collectives.json`` (schema v2): ``run_overlap_wire`` pits the
serial monolithic tree pipeline against the backward-overlapped bucketed
wire (``repro.dist.overlap``) on a layer-spectrum tree — claim: bucketed
beats serial outright and by ≥ 25% — and ``run_metrics_fetch`` measures
the before/after of killing the driver's per-step host metrics sync
(``launch/train.py`` now drains at log points only).
``run_zero_groupaligned`` adds the sharded schedule: the group-aligned
ZeRO two-leg pipeline (per-bucket int8 ``zero_bucketed_reduce_scatter``
+ one int8 ``zero_allgather_params``) against the fp32 reduce-scatter +
all-gather over the SAME flat layout — claim: the int8 two-leg wire
moves ≤ 0.26× the fp32 bytes, alignment padding included.

Second artifact (``results/bench/wire_controller.json``): LeNet/MNIST-tiny
loss trajectories under the paper's hair-trigger ``r_max = 1e-4`` at 8
wire bits, comparing **wire-domain controller kinds** — the shared-IL-style
threshold-driven ``paper`` wire (⟨IL, 8−IL⟩ with IL ratcheting on stray
wire clips, the dynamics the pre-registry derived-format design exhibited),
``courbariaux`` (overflow-driven radix with a decay path), and the default
dedicated ``flexpoint`` wire (max-abs-driven radix).  This is the measured
basis for "choosing a wire controller" in dist/README.md.

Run standalone (multi-device): ``PYTHONPATH=src python -m
benchmarks.bench_collectives`` — the module forces an 8-way host platform
before JAX initializes.  Under ``benchmarks.run`` (JAX already live with
one device) it degrades to a note.
"""

from __future__ import annotations

import os

# only the standalone entry point (python -m benchmarks.bench_collectives)
# may mutate process-global XLA flags, and only before JAX initializes; a
# plain import (benchmarks.run, pytest collection) must stay side-effect
# free.
if __name__ == "__main__" and "jax" not in __import__("sys").modules:
    os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                               + os.environ.get("XLA_FLAGS", ""))

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from benchmarks.common import is_quick, save_result
from repro.core.fixed_point import FixedPointFormat
from repro.dist.collectives import (dps_allreduce_mean,
                                    dps_allreduce_mean_tree)
from repro.dist.sharding import make_mesh
from repro.launch.hlo_stats import collective_wire_bytes, concat_bytes


def hbm_traffic_model(size: int, n_dev: int, variant: str) -> float:
    """Modeled HBM bytes ONE rank moves per all-reduce (both legs).

    E = local elements, c = E / n (the owned chunk).  The model counts
    tensor-sized reads/writes only (stats and scalars are noise):

      fp32          read 4E + write 4E (the stock all-reduce's copy in/out)
      int8 fused    encode read 4E (+4E rounding bits) + write E int8;
                    receive read E int8 + write 4c fp32 mean (the fused
                    decode-reduce never materializes the (n, c) fp32
                    stack); leg-2 encode read 4c + write c int8; gather
                    decode read E + write 4E
      int8 jnp      the same, plus the receive leg's 4E fp32 write + 4E
                    read for the decoded (n, c) stack (and, for layouts
                    that are not already group-aligned, an 8E fp32
                    align/scatter pass the benchmark's exact layout
                    skips)
    """
    E = float(size)
    c = E / n_dev
    if variant == "fp32":
        return 8 * E
    fused = (4 * E + 4 * E + E) + (E + 4 * c) + (4 * c + c) + (E + 4 * E)
    if variant.startswith("int8_kernel"):
        return fused
    naive_receive = 4 * E + 4 * E          # fp32 (n, c) stack write + read
    return fused + naive_receive


def run_wire_controllers(mesh, steps: int):
    """Train LeNet/MNIST-tiny at hair-trigger ``r_max`` per wire controller.

    The ``paper`` variant is the shared-IL-style baseline: a threshold-
    driven wire domain whose IL moves on every step with > 0.01% wire
    clipping and whose FL is pinned to the remaining bits — the ⟨IL, 8−IL⟩
    ratchet dynamics the pre-registry design derived from the grads
    controller.  ``flexpoint`` is the registry default (radix from the
    running max|g|, two octaves of bulk bias — ``dps.wire_hyper``).
    """
    from jax.sharding import NamedSharding
    from repro.core import qtrain
    from repro.core.dps import DPSHyper, wire_hyper
    from repro.data import MNISTLike
    from repro.models import lenet
    from repro.optim import SGDConfig, make_optimizer

    opt = make_optimizer(SGDConfig())
    data = MNISTLike(batch=64, seed=0)
    params = lenet.init(jax.random.key(0))
    hg = DPSHyper(il_init=6, fl_init=12, e_max=5e-2, r_max=1e-4)
    batch_sh = {"images": NamedSharding(mesh, P("data")),
                "labels": NamedSharding(mesh, P("data"))}

    def run_one(wire_controller):
        qcfg = qtrain.QuantConfig(
            enabled=True, hyper_grads=hg, grad_allreduce_bits=8,
            wire_controller=wire_controller,
            # same initial placement for every kind; flexpoint's slack is
            # what wire_hyper would default anyway
            hyper_wire_grads=wire_hyper(8, il_init=6, slack=-2.0))
        state = qtrain.TrainState.create(params, opt.init(params), qcfg,
                                         jax.random.key(1))
        repl = jax.tree.map(lambda _: NamedSharding(mesh, P()), state)
        step = qtrain.make_train_step(lenet.loss_fn, opt, qcfg, mesh=mesh)
        jitted = jax.jit(step, in_shardings=(repl, batch_sh),
                         out_shardings=None)
        hist = {"loss": [], "il_wire": [], "fl_wire": [], "fl_g": [],
                "R_wire": []}
        for i in range(steps):
            state, m = jitted(state, data.train_batch(i))
            hist["loss"].append(float(m["loss"]))
            hist["il_wire"].append(float(m["il_wire_grads"]))
            hist["fl_wire"].append(float(m["fl_wire_grads"]))
            hist["fl_g"].append(float(m["fl_g"]))
            hist["R_wire"].append(float(m["R_wire"]))
        tail = float(np.mean(hist["loss"][-max(5, steps // 4):]))
        il = hist["il_wire"]
        return {
            "history": hist,
            "loss_start": hist["loss"][0],
            "loss_tail_mean": tail,
            "loss_peak": max(hist["loss"]),
            "wire_il_up_events": sum(1 for a, b in zip(il, il[1:]) if b > a),
            "wire_il_final": il[-1],
            "compute_fl_max": max(hist["fl_g"]),
            "converged": bool(np.isfinite(hist["loss"]).all()
                              and tail < 0.6 * hist["loss"][0]),
        }

    variants = {k: run_one(k) for k in ("paper", "courbariaux", "flexpoint")}
    flex = variants["flexpoint"]
    out = {
        "n_devices": mesh.devices.size,
        "steps": steps,
        "scenario": "LeNet/MNIST-tiny, r_max=1e-4 (hair-trigger), "
                    "8 wire bits, grads hyper <6,12> e_max=5e-2",
        "per_controller": variants,
        "claims": {
            # the redesign's guarantee: the default dedicated wire
            # controller trains stably where the shared-IL-style ratchet
            # was pinned as unstable (the paper/courbariaux rows document
            # whatever the threshold-driven kinds do — reported, not
            # asserted)
            "flexpoint_converges": flex["converged"],
            "flexpoint_compute_fl_off_rail":
                flex["compute_fl_max"] < hg.fl_max,
        },
    }
    save_result("wire_controller", out)
    return out


def _time_variants(fns: dict, args, iters: int) -> dict:
    """Best-of-``iters`` ms per step for every variant, measured
    ROUND-ROBIN: one step of each variant per round, so slow phases of a
    shared CPU box hit all variants alike and the walltime-RATIO claims
    compare like with like.  Min-of-rounds is robust to scheduler noise.

    Timing honesty rule: every variant's ``fn`` must return (and we block
    on) the FINAL DECODED OUTPUT only — the fp32 mean a training step
    would consume next.  Stats, intermediates, and per-bucket partial
    results are dropped inside the jit, for every variant alike; a
    variant must never pay a sync another variant skips.
    """
    for fn in fns.values():                     # compile + warm
        jax.block_until_ready(fn(*args))
    best = {name: float("inf") for name in fns}
    for _ in range(iters):
        for name, fn in fns.items():
            t0 = time.time()
            jax.block_until_ready(fn(*args))
            best[name] = min(best[name], time.time() - t0)
    return {name: t * 1e3 for name, t in best.items()}


def _time_steps(fn, args, iters: int) -> float:
    return _time_variants({"_": fn}, args, iters)["_"]


def run_overlap_wire(mesh, iters: int, total: int):
    """Serial-monolithic vs bucketed wire on a layer-spectrum tree.

    Both variants compress the SAME gradient-shaped tree with the same
    per-leaf [G] format table and run the same two-leg int8 schedule; the
    serial variant is the monolithic ``dps_allreduce_mean_tree`` (one
    collective pair over one packed buffer), the overlap variant is
    ``repro.dist.overlap.bucketed_allreduce_mean_tree`` (one pair per
    bucket, backward ready order, per-bucket size-aware quanta).  On this
    single-core CPU box there is no compute to hide the collectives
    behind, so the measured gap is the overlap schedule's OTHER wins —
    cache locality of bucket-sized working sets and tighter per-bucket
    alignment padding — which is what the ≥25% claim pins.
    """
    from repro.dist import overlap as overlap_lib

    n_dev = mesh.devices.size
    # layer-like spectrum: a few big tensors + a tail of small ones,
    # deliberately not quantum-divisible
    sizes = [total // 2, total // 4, total // 8, total // 16, total // 32]
    sizes.append(total - sum(sizes))
    sizes = tuple(sizes)
    G = len(sizes)
    fmt = FixedPointFormat(
        jnp.array([[3, 2, 4, 3][g % 4] for g in range(G)], jnp.int32),
        jnp.array([[5, 6, 4, 5][g % 4] for g in range(G)], jnp.int32))
    key = jax.random.key(2)
    tree = {f"layer{i}": jax.random.normal(jax.random.fold_in(key, 100 + i),
                                           (n_dev, s)) * 0.5
            for i, s in enumerate(sizes)}
    target = max(total // 8, 1)

    def serial_body(tr, k):
        m, _ = dps_allreduce_mean_tree(tr, fmt, "data", k)
        return m

    def overlap_body(tr, k):
        from repro.dist.overlap import bucketed_allreduce_mean_tree
        m, _ = bucketed_allreduce_mean_tree(tr, fmt, "data", k,
                                            target_elems=target)
        return m

    plan = overlap_lib.plan_buckets(sizes, target)
    fns, stats = {}, {}
    for name, body in (("serial", serial_body), ("overlap", overlap_body)):
        fn = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=({k: P("data", None) for k in tree}, P()),
            out_specs=P(), check_vma=False))
        hlo = fn.lower(tree, key).compile().as_text()
        wire = collective_wire_bytes(hlo)
        fns[name] = fn
        stats[name] = {"wire_bytes": wire["total"],
                       "wire_bytes_by_dtype": wire["by_dtype"]}
    # both bodies return the decoded mean tree only (the timing honesty
    # rule _time_variants documents): neither variant syncs on stats
    times = _time_variants(fns, (tree, key), iters)
    for name, ms in times.items():
        stats[name]["ms_per_step"] = ms
    improvement = 1.0 - times["overlap"] / times["serial"]
    return {
        "leaf_sizes": list(sizes),
        "total_elems": total,
        "bucket_target_elems": target,
        "n_buckets": plan.n_buckets,
        "per_variant": stats,
        "overlap_improvement_over_serial": improvement,
    }


def run_zero_groupaligned(mesh, iters: int, total: int):
    """Group-aligned ZeRO two-leg wire vs fp32 over the SAME flat layout.

    Both variants move one gradient-sized tree through a reduce-scatter
    and bring the full flat vector back with an all-gather, over the
    identical :class:`~repro.dist.sharding.GroupAlignedPartitioner`
    layout (same buckets, same alignment padding) — so the wire-byte
    ratio isolates the codec, not the layout.  The int8 variant is the
    sharded train-step pipeline itself: per-bucket
    ``zero_bucketed_reduce_scatter`` in backward-ready order (per-leaf
    [G] formats) + one concatenated ``zero_allgather_params``.  Walltime
    is reported for completeness but the claim is bytes-only: the jnp
    codec's emulation cost on CPU is not a wire measurement.
    """
    from repro.dist import overlap as overlap_lib
    from repro.dist.sharding import GroupAlignedPartitioner

    n_dev = mesh.devices.size
    sizes = [total // 2, total // 4, total // 8, total // 16, total // 32]
    sizes.append(total - sum(sizes))
    sizes = tuple(sizes)
    G = len(sizes)
    fmt_g = FixedPointFormat(
        jnp.array([[3, 2, 4, 3][g % 4] for g in range(G)], jnp.int32),
        jnp.array([[5, 6, 4, 5][g % 4] for g in range(G)], jnp.int32))
    key = jax.random.key(3)
    tree = {f"layer{i}": jax.random.normal(jax.random.fold_in(key, 200 + i),
                                           (n_dev, s)) * 0.5
            for i, s in enumerate(sizes)}
    target = max(total // 8, 1)
    plan = overlap_lib.plan_buckets(sizes, target)
    abstract = {n: jax.ShapeDtypeStruct((s,), jnp.float32)
                for n, s in zip(tree, sizes)}
    # flatten-order buckets, exactly like qtrain.zero_partitioner
    part = GroupAlignedPartitioner.create(
        abstract, n_dev, backend="jnp",
        buckets=tuple(sorted(plan.buckets, key=lambda r: r[0])))

    def local_tree(tr):
        return {n: v.reshape(-1) for n, v in tr.items()}

    def zero_body(tr, k):
        # same key to both legs, like the train step (the internal fold
        # constants keep the two draw streams disjoint)
        gshard, _ = overlap_lib.zero_bucketed_reduce_scatter(
            local_tree(tr), fmt_g, "data", k, part=part, backend="jnp")
        flat, _ = overlap_lib.zero_allgather_params(
            gshard, fmt_g, "data", k, part=part, backend="jnp")
        return flat

    def fp32_body(tr, k):
        flat = part.flatten(local_tree(tr))
        gshard = jax.lax.psum_scatter(flat, "data", scatter_dimension=0,
                                      tiled=True) / n_dev
        return jax.lax.all_gather(gshard, "data", axis=0, tiled=True)

    fns, stats = {}, {}
    for name, body in (("fp32", fp32_body), ("zero_groupaligned", zero_body)):
        fn = jax.jit(jax.shard_map(
            body, mesh=mesh,
            in_specs=({k: P("data", None) for k in tree}, P()),
            out_specs=P(), check_vma=False))
        hlo = fn.lower(tree, key).compile().as_text()
        wire = collective_wire_bytes(hlo)
        fns[name] = fn
        stats[name] = {"wire_bytes": wire["total"],
                       "wire_bytes_by_dtype": wire["by_dtype"]}
    times = _time_variants(fns, (tree, key), iters)
    for name, ms in times.items():
        stats[name]["ms_per_step"] = ms
    ratio = (stats["zero_groupaligned"]["wire_bytes"]
             / stats["fp32"]["wire_bytes"])
    return {
        "leaf_sizes": list(sizes),
        "total_elems": total,
        "padded_elems": part.padded_size,
        "n_buckets": part.n_buckets,
        "per_variant": stats,
        "wire_ratio_int8_over_fp32": ratio,
    }


def run_metrics_fetch(mesh, steps: int):
    """Per-step host sync vs deferred metrics fetch on a compressed step.

    The serial driver fetched every step's metrics to Python floats
    before issuing the next step — a host round-trip on the critical path
    that also fences the overlap schedule (nothing can stay in flight
    across a blocking fetch).  The overlap-aware driver
    (``repro.launch.train``) keeps metrics on device and drains them at
    log points only.  Both loops run the SAME jitted compressed step and
    block on the final state at the end, so the difference is purely the
    per-step host sync.
    """
    from jax.sharding import NamedSharding
    from repro.core import qtrain
    from repro.data import MNISTLike
    from repro.models import lenet
    from repro.optim import SGDConfig, make_optimizer

    opt = make_optimizer(SGDConfig())
    data = MNISTLike(batch=64, seed=0)
    params = lenet.init(jax.random.key(0))
    qcfg = qtrain.QuantConfig(enabled=True, grad_allreduce_bits=8)
    state0 = qtrain.TrainState.create(params, opt.init(params), qcfg,
                                      jax.random.key(1))
    batch_sh = {"images": NamedSharding(mesh, P("data")),
                "labels": NamedSharding(mesh, P("data"))}
    repl = jax.tree.map(lambda _: NamedSharding(mesh, P()), state0)
    step = jax.jit(qtrain.make_train_step(lenet.loss_fn, opt, qcfg,
                                          mesh=mesh),
                   in_shardings=(repl, batch_sh), out_shardings=None)
    batches = [data.train_batch(i) for i in range(steps)]
    state, m = step(state0, batches[0])            # compile + warm
    jax.block_until_ready((state, m))

    def synced():
        st, out = state0, []
        for b in batches:
            st, m = step(st, b)
            out.append(float(m["loss"]))           # host sync per step
        jax.block_until_ready(st)
        return out

    def deferred():
        st, pending = state0, []
        for b in batches:
            st, m = step(st, b)
            pending.append(m)                      # stays on device
        jax.block_until_ready(st)
        return [float(m["loss"]) for m in pending]

    # warm both loops, then time them ROUND-ROBIN min-of-rounds like
    # _time_variants — a single back-to-back pair is at the mercy of
    # whatever else the box is doing for those few seconds
    assert synced() == deferred()                  # fetch mode is metadata
    best = {"synced": float("inf"), "deferred": float("inf")}
    for _ in range(4):
        for name, loop in (("synced", synced), ("deferred", deferred)):
            t0 = time.time()
            loop()
            best[name] = min(best[name], time.time() - t0)
    return {
        "steps": steps,
        "synced_ms_per_step": best["synced"] / steps * 1e3,
        "deferred_ms_per_step": best["deferred"] / steps * 1e3,
        "deferred_improvement": 1.0 - best["deferred"] / best["synced"],
    }


def run():
    n_dev = jax.device_count()
    if n_dev < 2:
        out = {"skipped": True,
               "note": "needs a multi-device mesh; run standalone "
                       "(python -m benchmarks.bench_collectives)"}
        save_result("collectives", out)
        return out

    mesh = make_mesh((n_dev,), ("data",))
    size = (1 << 21) if is_quick() else (1 << 24)     # fp32 elements per rank
    iters = 3 if is_quick() else 20
    fmt = FixedPointFormat.create(3, 5)
    # per-group table: one ⟨IL, FL⟩ per layer-sized group, radices spread
    # over 3 octaves like real per-layer gradient ranges.  The quantum is
    # one (256, 1024) kernel tile and every group size is a multiple of
    # it, so the grouped grid matches the global kernel's tile geometry
    # EXACTLY (same tile count, same tile shape, identity align): the
    # walltime ratio isolates the [G, 2]-table machinery — the honest
    # apples-to-apples comparison, and the right real-HW configuration
    # for multi-MiB layers (the 4096 default quantum is sized for trees
    # of many small leaves instead)
    quantum = 1 << 18                      # = one (256, 1024) kernel tile
    G = 8
    fmt_g = FixedPointFormat(
        jnp.array([[3, 2, 4, 3][g % 4] for g in range(G)], jnp.int32),
        jnp.array([[5, 6, 4, 5][g % 4] for g in range(G)], jnp.int32))
    group_sizes = tuple([size // G] * G)
    x = jax.random.normal(jax.random.key(0), (n_dev, size)) * 0.5
    key = jax.random.key(1)

    def fp32_body(xs, key):
        return jax.lax.pmean(xs[0], "data")

    def int8_body(backend, grouped=False):
        def body(xs, key):
            m, _ = dps_allreduce_mean(
                xs[0], fmt_g if grouped else fmt, "data", key,
                backend=backend,
                group_sizes=group_sizes if grouped else None,
                quantum=quantum)
            return m
        return body

    variants = {}
    results = {}
    for name, body in (("fp32", fp32_body),
                       ("int8_jnp", int8_body("jnp")),
                       ("int8_kernel", int8_body("kernel")),
                       ("int8_jnp_grouped", int8_body("jnp", grouped=True)),
                       ("int8_kernel_grouped",
                        int8_body("kernel", grouped=True))):
        fn = jax.jit(jax.shard_map(body, mesh=mesh,
                                   in_specs=(P("data", None), P()),
                                   out_specs=P(), check_vma=False))
        hlo = fn.lower(x, key).compile().as_text()
        wire = collective_wire_bytes(hlo)
        variants[name] = fn
        results[name] = {"wire_bytes": wire["total"],
                         "wire_bytes_by_dtype": wire["by_dtype"],
                         "hbm_model_bytes_per_rank":
                             hbm_traffic_model(size, n_dev, name)}
    # interleaved timing: the grouped-vs-global kernel ratio claim needs
    # both sides measured under the same machine conditions
    times = _time_variants(variants, (x, key), max(iters, 5))
    for name, ms in times.items():
        results[name]["ms_per_step"] = ms

    # the codecs draw identical rounding bits from the same key, so the
    # collective's result must be bit-identical across backends — for the
    # global AND the grouped format table.
    codecs_bitexact = bool(jnp.array_equal(variants["int8_jnp"](x, key),
                                           variants["int8_kernel"](x, key)))
    grouped_bitexact = bool(jnp.array_equal(
        variants["int8_jnp_grouped"](x, key),
        variants["int8_kernel_grouped"](x, key)))

    ratio = results["int8_jnp"]["wire_bytes"] / results["fp32"]["wire_bytes"]
    grouped_wall_ratio = (results["int8_kernel_grouped"]["ms_per_step"]
                          / results["int8_kernel"]["ms_per_step"])
    grouped_wire_ratio = (results["int8_kernel_grouped"]["wire_bytes"]
                          / results["fp32"]["wire_bytes"])

    # --- rebuilt tree all-reduce: no fp32 flat-concat in the HLO ---
    tree = {f"layer{i}": jax.random.normal(jax.random.fold_in(key, i),
                                           (n_dev, s)) * 0.5
            for i, s in enumerate((48000, 1200, 30720, 120, 840, 10))}
    tree_elems = sum(v.shape[1] for v in tree.values())
    fmt_tree = FixedPointFormat(
        jnp.array([3, 2, 4, 3, 2, 3], jnp.int32),
        jnp.array([5, 6, 4, 5, 6, 5], jnp.int32))
    tree_stats = {}
    for tname, tfmt in (("global", fmt), ("per_layer", fmt_tree)):
        def tree_body(tr, key, _f=tfmt):
            m, _ = dps_allreduce_mean_tree(tr, _f, "data", key)
            return m
        fn = jax.jit(jax.shard_map(
            tree_body, mesh=mesh,
            in_specs=({k: P("data", None) for k in tree}, P()),
            out_specs=P(), check_vma=False))
        hlo = fn.lower(tree, key).compile().as_text()
        cat = concat_bytes(hlo)
        wire = collective_wire_bytes(hlo)
        ms = _time_steps(fn, (tree, key), iters)
        tree_stats[tname] = {
            "f32_concat_bytes": cat["by_dtype"].get("f32", 0.0),
            "concat_bytes_by_dtype": cat["by_dtype"],
            "wire_bytes": wire["total"],
            "ms_per_step": ms,
        }
    tree_f32_concat = max(t["f32_concat_bytes"]
                          for t in tree_stats.values())
    # threshold: anything tree-sized would mean the flat-concat came back;
    # stats-stacking noise is a few hundred bytes
    tree_no_f32_concat = tree_f32_concat < 0.01 * 4 * tree_elems

    # the x-sized buffers are dead past this point; release them before
    # the overlap phase allocates its own tree at the same scale
    del variants, x

    # backward-overlapped bucketed wire vs the serial monolithic pipeline
    # the 25%-improvement claim needs a converged min-of-rounds on
    # a noisy 1-core box: 16 rounds (~13 s) instead of quick's 3
    overlap = run_overlap_wire(mesh, max(iters, 16), size)
    zero_ga = run_zero_groupaligned(mesh, iters, size)
    fetch = run_metrics_fetch(mesh, steps=12 if is_quick() else 30)

    # wire-domain controller comparison (shared-IL-style vs dedicated);
    # 40+ steps like the pinned stability test — the hair-trigger scenario
    # needs the post-transient window for an honest tail mean
    wire_ctrl = run_wire_controllers(mesh, steps=40 if is_quick() else 60)

    out = {
        "n_devices": n_dev,
        "elements_per_rank": size,
        "wire_groups": G,
        "group_quantum": quantum,
        "fp32_wire_bytes": results["fp32"]["wire_bytes"],
        "int8_wire_bytes": results["int8_jnp"]["wire_bytes"],
        "wire_ratio_int8_over_fp32": ratio,
        "grouped_wire_ratio_int8_over_fp32": grouped_wire_ratio,
        "grouped_kernel_walltime_over_global_kernel": grouped_wall_ratio,
        "per_variant": results,
        "tree_allreduce": tree_stats,
        "overlap": overlap,
        "zero_groupaligned": zero_ga,
        "metrics_fetch": fetch,
        "codecs_bitexact": codecs_bitexact,
        "grouped_codecs_bitexact": grouped_bitexact,
        "wire_controller": wire_ctrl,
        "note": "CPU container: int8_kernel runs the Pallas codec in "
                "interpret mode (numerics only; walltime not a kernel "
                "measurement)",
        "claims": {
            "int8_wire_le_quarter_fp32": ratio <= 0.26,
            "codec_backends_bitexact": codecs_bitexact,
            "grouped_codec_backends_bitexact": grouped_bitexact,
            # grouped wire overhead = group/chunk alignment padding only
            "grouped_wire_le_quarter_fp32": grouped_wire_ratio <= 0.26,
            # interpret-mode walltime is emulation cost (see module
            # docstring) and its grouped/global ratio moves with the host
            # CPU — measured 1.01 and 1.21 on two different boxes for the
            # SAME code.  The bound catches the failure mode that matters
            # (a mis-tiled [G, 2]-table path runs 20-30x, not 1.2x).
            "grouped_kernel_within_1p35x_of_global":
                grouped_wall_ratio <= 1.35,
            "tree_allreduce_no_f32_flat_concat": tree_no_f32_concat,
            # the overlapped bucketed wire must beat the serial monolithic
            # pipeline outright, and by >= 25% (cache locality + per-bucket
            # quanta on this box; on real hardware the collective also
            # hides behind backward compute)
            "overlap_faster_than_serial":
                overlap["per_variant"]["overlap"]["ms_per_step"]
                < overlap["per_variant"]["serial"]["ms_per_step"],
            "overlap_ge_25pct_over_serial":
                overlap["overlap_improvement_over_serial"] >= 0.25,
            # the sharded two-leg pipeline ships int8 both ways over the
            # group-aligned layout; the bound includes alignment padding
            "zero_groupaligned_wire_le_quarter_fp32":
                zero_ga["wire_ratio_int8_over_fp32"] <= 0.26,
            # on this 1-core emulation the step executes serially either
            # way, so deferring the host fetch is a wash (measured: 1-6%
            # slower from the deeper async dispatch queue) — the claim
            # bounds it at noise level; the actual win needs hardware
            # where a blocked host thread stalls the dispatch pipeline
            "deferred_fetch_within_noise":
                fetch["deferred_ms_per_step"]
                <= 1.10 * fetch["synced_ms_per_step"],
            **wire_ctrl["claims"],
        },
    }
    save_result("collectives", out)
    return out


if __name__ == "__main__":
    import json
    print(json.dumps(run(), indent=1, default=float))
