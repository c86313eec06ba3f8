"""Quantized-training plumbing: taps, precision-domain registry, train-state.

Wires the paper's Algorithm 1 into an arbitrary JAX model:

  forward pass   — activations pass through :func:`act_tap` (quantize + stats
                   on the way down, gradient quantization on the way back up
                   via ``custom_vjp``),
  backward pass  — parameter gradients are quantized before the optimizer;
                   the loss's own logit-gradient (the paper's "last layer
                   gradients") is quantized with stats,
  weight update  — updated weights are re-snapped to the weight grid
                   (stochastic rounding makes tiny updates survive in
                   expectation, the property Gupta et al. identified),
  scale_precision — one controller per **precision domain** consumes the
                   step's merged stats and emits the next step's ⟨IL, FL⟩.

Precision domains generalize the paper's fixed weights/acts/grads triple: a
:class:`~repro.core.dps.PrecisionPlan` (``QuantConfig.plan()``) declares a
named registry of ``{domain: controller kind, hyper, stats routing, group
count}`` that builds the pytree :class:`~repro.core.dps.DpsBundle` threaded
through :class:`TrainState`.  The standard plan carries the three compute
domains plus dedicated **wire domains** when compressed gradient sync is on:

  ``wire_grads``   — owns the int8 format of the gradient all-reduce /
                     reduce-scatter leg, fed by that leg's wire QuantStats
                     (default controller "flexpoint": max-abs-driven radix,
                     Köster et al.);
  ``wire_params``  — owns the ZeRO-1 parameter all-gather leg's format,
                     fed by the params-leg wire stats.

Wire stats feed *only* their wire domain — never the compute controllers.
Deriving the wire grid from the grads controller's IL (the pre-registry
``wire_format``-of-the-compute-format scheme) let a few clipped wire
elements ratchet IL up, coarsen the ⟨IL, 8−IL⟩ wire grid, and rail the
compute FL at its cap chasing irreducible wire error (the instability
pinned — now as a stability guarantee — by
``tests/test_train_allreduce.py``).

Everything here is shape-polymorphic and mesh-agnostic: stats are plain
``jnp`` reductions, so under ``pjit`` they come out globally reduced, and the
⟨IL, FL⟩ state is replicated.

The step's passes carry ``jax.named_scope`` names, which reach the compiled
program's ``op_name`` metadata and change nothing else, so a profile can
attribute device time to them: ``dps.weights`` (the weight snap and
re-snap), ``dps.acts`` (the forward taps), ``dps.grads`` (the backward taps
and the optimizer-input gradient quantization) and ``optim`` (the optimizer
update and its apply).
"""

from __future__ import annotations

import dataclasses
import math
import warnings
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import dps as dps_lib
from repro.core import fixed_point as fxp
from repro.core.dps import DpsBundle, DomainSpec, PrecisionPlan
from repro.core.fixed_point import FixedPointFormat, QuantStats
from repro.core.policy import QuantPolicy
from repro.device import on_tpu
from repro.kernels import ops as kernel_ops


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Static configuration of the quantized-training scheme."""

    enabled: bool = True
    controller: str = "paper"
    rounding: str = fxp.ROUND_STOCHASTIC
    policy: QuantPolicy = QuantPolicy()
    # one hyper per compute domain; the paper runs one Alg.-2 instance each
    # for weights, activations and gradients (global granularity).
    hyper_weights: dps_lib.DPSHyper = dps_lib.DPSHyper()
    hyper_acts: dps_lib.DPSHyper = dps_lib.DPSHyper()
    hyper_grads: dps_lib.DPSHyper = dps_lib.DPSHyper(il_init=8, fl_init=16)
    stat_scope: str = "global"          # "global" | "last_layer"
    master_weights: bool = False        # keep an fp copy (beyond-paper)
    # Wire precision domains: with compressed gradient sync on, each int8
    # collective leg runs its own controller — "wire_grads" for the gradient
    # scatter/all-reduce leg, "wire_params" for the ZeRO parameter all-gather
    # leg — instead of deriving ⟨IL, 8−IL⟩ from a compute controller (the
    # ratchet failure documented in dist/README.md).  "flexpoint" places the
    # wire radix just above the observed max |x| at a fixed wire width, so
    # stray clipped elements cannot ratchet the grid coarser.
    wire_controller: str = "flexpoint"
    hyper_wire_grads: Optional[dps_lib.DPSHyper] = None   # None -> derived
    hyper_wire_params: Optional[dps_lib.DPSHyper] = None  # None -> derived
    # Measured wire slack: derive each wire domain's radix headroom from
    # its own measured abs_sum/nonzero tail quantile instead of the
    # hand-tuned per-tensor-class constants (dps.wire_hyper(auto_slack=
    # True)).  Only affects the DERIVED wire hypers — an explicit
    # hyper_wire_* wins.
    wire_auto_slack: bool = False
    # Per-LAYER wire formats: 0 = one global wire ⟨IL, FL⟩ (scalar state);
    # G > 0 gives the ``wire_grads`` domain a [G] controller state — one
    # ⟨IL, FL⟩ per gradient-tree leaf, fed group-wise by the collective's
    # [G] wire stats and handed to the group-aligned collectives as the
    # [G, 2] kernel format table.  G must equal the grad tree's leaf count
    # when the compressed sync engages (``make_train_step`` checks);
    # ``with_per_layer_wire`` derives it from a params tree.  Under
    # ``zero_opt_shards`` the flat optimizer layout switches to the
    # group-aligned :class:`~repro.dist.sharding.GroupAlignedPartitioner`
    # (leaf slots padded to the wire quantum), so per-leaf boundaries —
    # and with them the per-leaf ⟨IL, FL⟩ — survive the flatten and both
    # sharded wire legs run the grouped codec.  ``wire_params`` mirrors
    # the group count: one params-leg format per leaf too.
    wire_grads_groups: int = 0
    # Full custom registry: overrides the standard five-domain plan built
    # from the fields above.
    precision_plan: Optional[PrecisionPlan] = None
    # Opt-in compressed gradient synchronization: when set (8 to start),
    # parameter gradients are averaged across the data axis by an explicit
    # shard_map'ed int8-wire ``dps_allreduce_mean`` instead of GSPMD's
    # implicit fp32 psum, and the wire-leg QuantStats merge into the grads
    # DPS stats — so wire quantization error steers ⟨IL, FL⟩.  Needs
    # ``make_train_step(..., mesh=...)``; degrades to the identity on
    # single-device meshes.
    grad_allreduce_bits: Optional[int] = None
    # Backward-overlapped bucketed wire (repro.dist.overlap): with the
    # compressed sync engaged, split the gradient tree into DDP-style
    # buckets (contiguous leaf runs in backward ready order — last layer
    # first) and run one compressed collective pair per bucket instead of
    # one monolithic pair for the tree.  Each bucket's wire legs depend
    # only on its own leaves, so collective dispatch can overlap the
    # remaining backward, working sets stay bucket-sized, and per-bucket
    # GroupLayouts shrink grouped-padding overhead.  Gradient-readiness
    # taps (custom-vjp identities on the params) mark each bucket's
    # materialization point in the backward jaxpr; the precision-flow
    # verifier's PF-BUCKET rules prove every bucket is encoded exactly
    # once and decoded before the optimizer consumes it.  No effect
    # without ``grad_allreduce_bits``.  Composes with
    # ``zero_opt_shards``: the group-aligned ZeRO layout materializes
    # each bucket as a contiguous run of aligned leaf slots, so the
    # sharded path runs one int8 reduce-scatter per bucket in the same
    # backward-ready order (the all-gather return leg stays monolithic —
    # it has no readiness structure to exploit).
    wire_overlap: bool = False
    wire_bucket_elems: int = 0          # 0 -> overlap.DEFAULT_BUCKET_ELEMS
    # Numeric health guards (repro.resilience): a GuardConfig arms the
    # on-device step health monitor — loss/gradient NaN detection with a
    # skip gate, per-wire-domain overflow-storm EWMAs, gradient-norm
    # spike detection, controller rail bits — and the graceful
    # degradation state machine that swaps a tripped wire domain's int8
    # collective for its fp32 fallback through a traced flag (both
    # branches live in the one compiled step; int8 re-arms after a
    # cooldown of clean steps).  None (the default) leaves the step's
    # jaxpr untouched; with guards armed and no fault the trajectory is
    # bit-exact with the unguarded step (see tests/test_resilience.py).
    guards: Optional[Any] = None
    # ZeRO-1: shard the optimizer state across the data axis into this many
    # slices (must equal the mesh's data-axis size when it engages).  The
    # param tree is flattened into a padded 1-D layout so non-divisible
    # leaves still shard — the plain ZeroPartitioner normally, or the
    # group-aligned :class:`~repro.dist.sharding.GroupAlignedPartitioner`
    # when per-layer wire formats or ``wire_overlap`` engage (see
    # :func:`zero_partitioner`); each rank steps its slice locally and
    # the updated parameter shards are all-gathered back.  Combined
    # with ``grad_allreduce_bits``, both collective legs (reduce-scatter of
    # grads, all-gather of params) ride the int8 wire.  Optimizer state is
    # created with :func:`zero_opt_state` instead of ``optimizer.init``.
    # Engages on pure data-parallel meshes only (same JAX partial-manual
    # shard_map constraint as the compressed all-reduce); degrades to the
    # replicated step on a single device or without a mesh.
    zero_opt_shards: Optional[int] = None

    def plan(self) -> PrecisionPlan:
        """The precision-domain registry this config trains under.

        The standard plan: one domain per compute attribute (same controller
        kind, per-domain hyper), plus ``wire_grads`` whenever
        ``grad_allreduce_bits`` is set and ``wire_params`` when ZeRO-1 can
        additionally put the parameter all-gather on the wire.  A custom
        ``precision_plan`` replaces all of it.
        """
        if self.precision_plan is not None:
            return self.precision_plan
        domains = [
            ("weights", DomainSpec(self.controller, self.hyper_weights)),
            ("acts", DomainSpec(self.controller, self.hyper_acts)),
            ("grads", DomainSpec(self.controller, self.hyper_grads)),
        ]
        wb = self.grad_allreduce_bits
        if wb is not None:
            # default radix placement mirrors the tensor class (see
            # dps.wire_hyper): gradients start wide (±2^5 covers typical
            # init grads) and track the bulk two octaves under the max
            # (slack -2: clip the rare tail, keep grid resolution);
            # parameters are O(1), concentrated, and bias under clipping,
            # so their radix covers the max with headroom (slack +1).
            # wire_grads_groups > 0 turns the domain per-layer: a [G]
            # controller state driving the [G, 2] kernel format table.
            domains.append(("wire_grads", DomainSpec(
                self.wire_controller,
                self.hyper_wire_grads
                or dps_lib.wire_hyper(wb, il_init=6, slack=-2.0,
                                      auto_slack=self.wire_auto_slack),
                groups=self.wire_grads_groups, wire=True)))
            if self.zero_opt_shards is not None:
                # wire_params mirrors the grads domain's granularity: the
                # group-aligned layout keeps leaf boundaries, so per-layer
                # wire runs one params-leg ⟨IL, FL⟩ per leaf as well.
                domains.append(("wire_params", DomainSpec(
                    self.wire_controller,
                    self.hyper_wire_params
                    or dps_lib.wire_hyper(wb, il_init=2, slack=1.0,
                                          auto_slack=self.wire_auto_slack),
                    groups=self.wire_grads_groups, wire=True)))
        return PrecisionPlan(tuple(domains))

    def with_per_layer_wire(self, params) -> "QuantConfig":
        """This config with one ``wire_grads`` format per leaf of
        ``params`` (a concrete or abstract tree) — the per-layer wire
        regime the group-aligned collectives run at kernel speed.  A
        no-op unless ``grad_allreduce_bits`` is set."""
        if self.grad_allreduce_bits is None or self.precision_plan is not None:
            return self
        return dataclasses.replace(
            self, wire_grads_groups=len(jax.tree_util.tree_leaves(params)))


def init_dps_bundle(qcfg: QuantConfig) -> DpsBundle:
    """Initial DPS registry: one controller state per declared domain."""
    return qcfg.plan().init()


def bundle_formats(qcfg: QuantConfig, bundle: DpsBundle
                   ) -> Dict[str, FixedPointFormat]:
    """Per-domain ⟨IL, FL⟩ for this step, keyed by domain name."""
    return qcfg.plan().formats(bundle)


def update_dps_bundle(qcfg: QuantConfig, bundle: DpsBundle,
                      streams: Dict[str, QuantStats], aux=None) -> DpsBundle:
    """scale_precision over the registry: each domain consumes the stats
    stream its spec routes to (absent streams read as zero stats)."""
    return qcfg.plan().update(bundle, streams, aux)


def dps_restore_defaults(qcfg: QuantConfig, prefix: str = ".dps") -> dict:
    """Checkpoint back-compat defaults: a fresh DPS registry, flattened to
    the checkpoint's ``".dps/<domain>/.<field>"`` key paths (the leading
    dots are how ``GetAttrKey`` stringifies — ``TrainState`` is a
    registered dataclass, so its checkpoint keys carry them).

    Pass as ``ckpt.restore(..., defaults=...)`` so a run configured with
    wire domains resumes from a legacy checkpoint that only carries the
    three-key compute bundle — the missing domains initialize fresh while
    everything present in the checkpoint restores normally.
    """
    from repro.checkpoint import flatten_tree  # deferred: io imports core
    return {f"{prefix}/{k}": v
            for k, v in flatten_tree(init_dps_bundle(qcfg)).items()}


def guard_restore_defaults(qcfg: QuantConfig, prefix: str = ".guard") -> dict:
    """Checkpoint back-compat defaults for the guard subtree: a run with
    ``qcfg.guards`` armed resumes from a checkpoint written without guards
    (the missing :class:`~repro.resilience.GuardState` initializes fresh).
    Empty when guards are off."""
    if qcfg.guards is None:
        return {}
    from repro.resilience import guards as guards_lib  # deferred
    return guards_lib.guard_restore_defaults(qcfg.plan(), prefix)


# ---------------------------------------------------------------------------
# Activation tap: quantize forward, quantize the cotangent backward.
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class QCtx:
    """Per-step quantization context handed to model code.

    ``None`` (the default ``QCtx.off()``-less path) disables taps entirely —
    model code guards with ``if qctx is not None``.
    """

    acts_fmt: FixedPointFormat
    grads_fmt: FixedPointFormat
    key: jax.Array
    rounding: str = dataclasses.field(metadata=dict(static=True))
    collect_stats: bool = dataclasses.field(metadata=dict(static=True))

    def tap(self, x: jax.Array, salt):
        """Quantize activation ``x``; returns ``(q, QuantStats)``.

        ``salt`` decorrelates rounding noise across call sites; inside a
        scanned stack pass the per-layer key/index.
        """
        kf = jax.random.fold_in(self.key, _salt_to_int(salt))
        kb = jax.random.fold_in(kf, 0x9E3779B9)
        q, stats = _qtap(self.rounding, x, self.acts_fmt, self.grads_fmt, kf, kb)
        if not self.collect_stats:
            stats = None
        return q, stats


def _salt_to_int(salt) -> jax.Array:
    if isinstance(salt, str):
        import zlib
        return jnp.uint32(zlib.crc32(salt.encode()))  # stable across processes
    return jnp.asarray(salt, jnp.uint32)


from functools import partial


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _qtap(mode, x, a_fmt, g_fmt, kf, kb):
    with jax.named_scope("dps.acts"):
        q, stats = fxp.quantize(x, a_fmt, mode=mode, key=kf,
                                compute_stats=True)
    return q, stats


def _qtap_fwd(mode, x, a_fmt, g_fmt, kf, kb):
    out = _qtap(mode, x, a_fmt, g_fmt, kf, kb)
    return out, (g_fmt, kb)


def _qtap_bwd(mode, res, cot):
    g_fmt, kb = res
    with jax.named_scope("dps.grads"):
        gq, _ = fxp.quantize(cot[0], g_fmt, mode=mode, key=kb,
                             compute_stats=False)
    return (gq, None, None, None, None)


_qtap.defvjp(_qtap_fwd, _qtap_bwd)


# ---------------------------------------------------------------------------
# Weight / gradient tree quantization.
# ---------------------------------------------------------------------------

def _leaf_quantizer(fused: bool):
    """The per-leaf quantizer of the tree passes: the fused Pallas kernel
    (on-chip PRNG, one ``pallas_call`` per leaf) or the jnp path."""
    return kernel_ops.dps_quantize_leaf if fused else fxp.quantize


def quantize_params(params, fmt: FixedPointFormat, qcfg: QuantConfig, key,
                    fused: bool = False):
    """Snap the parameter tree to the weight grid. Returns (qparams, stats).

    ``fused`` quantizes each leaf with the fused kernel (see
    ``make_train_step``'s ``fused_quant_active``)."""
    if not qcfg.enabled or not qcfg.policy.quantizes("weights"):
        return params, QuantStats.zero()
    with jax.named_scope("dps.weights"):
        return fxp.quantize_tree(params, fmt, mode=qcfg.rounding, key=key,
                                 predicate=qcfg.policy.param_predicate(),
                                 quantize_fn=_leaf_quantizer(fused))


def quantize_grads(grads, fmt: FixedPointFormat, qcfg: QuantConfig, key,
                   fused: bool = False):
    """Quantize parameter gradients before the optimizer step."""
    if not qcfg.enabled or not qcfg.policy.quantizes("grads"):
        return grads, QuantStats.zero()
    with jax.named_scope("dps.grads"):
        return fxp.quantize_tree(grads, fmt, mode=qcfg.rounding, key=key,
                                 predicate=qcfg.policy.param_predicate(),
                                 quantize_fn=_leaf_quantizer(fused))


# ---------------------------------------------------------------------------
# Train state + generic quantized train step.
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class TrainState:
    step: jax.Array
    params: Any
    opt_state: Any
    dps: Any                 # {attr: controller state}
    rng: jax.Array
    # rolling telemetry (replicated scalars) for logging/benchmarks:
    last_loss: jax.Array
    # health-guard state (repro.resilience.GuardState) when
    # ``qcfg.guards`` is armed; None keeps the legacy six-field pytree
    # (an empty subtree — old checkpoints restore without defaults).
    guard: Any = None

    @staticmethod
    def create(params, opt_state, qcfg: QuantConfig, rng) -> "TrainState":
        guard = None
        if qcfg.guards is not None:
            from repro.resilience import guards as guards_lib  # deferred
            guard = guards_lib.init_guard_state(qcfg.plan())
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            opt_state=opt_state,
            dps=init_dps_bundle(qcfg),
            rng=rng,
            last_loss=jnp.zeros((), jnp.float32),
            guard=guard,
        )


def _mesh_axis_sizes(mesh) -> Dict[str, int]:
    return (dict(zip(mesh.axis_names, mesh.devices.shape))
            if mesh is not None else {})


def zero_opt_engaged(qcfg: QuantConfig, mesh, data_axis: str = "data") -> bool:
    """Does the ZeRO-1 sharded-optimizer path engage for (qcfg, mesh)?

    Mirrors :func:`make_train_step`'s own checks so launch code and specs
    can size/shard the optimizer state consistently with the step that will
    actually run: requires ``zero_opt_shards`` set AND equal to the mesh's
    ``data_axis`` size (larger than 1), and a pure data-parallel mesh
    (every other axis of size 1 — the partial-manual shard_map
    constraint).  Any mismatch means the step warns and falls back to the
    replicated optimizer state, so this returns False for it too.
    """
    if qcfg.zero_opt_shards is None:
        return False
    sizes = _mesh_axis_sizes(mesh)
    n_data = int(sizes.get(data_axis, 1))
    if n_data <= 1 or qcfg.zero_opt_shards != n_data:
        return False
    return not any(s > 1 for a, s in sizes.items() if a != data_axis)


def wire_sync_engaged(qcfg: QuantConfig, mesh,
                      data_axis: str = "data") -> bool:
    """Does the compressed gradient all-reduce engage for (qcfg, mesh)?

    Mirrors :func:`make_train_step`'s own checks (the same pure
    data-parallel constraint as :func:`zero_opt_engaged`) so launch and
    analysis code can predict — without building the step — whether the
    ``wire_grads`` domain will actually put payload on the wire.
    """
    if qcfg.grad_allreduce_bits is None:
        return False
    sizes = _mesh_axis_sizes(mesh)
    if int(sizes.get(data_axis, 1)) <= 1:
        return False
    return not any(s > 1 for a, s in sizes.items() if a != data_axis)


def wire_params_engaged(qcfg: QuantConfig, params, mesh,
                        data_axis: str = "data") -> bool:
    """Does the ZeRO-1 parameter all-gather ride the int8 wire?

    The flat wire legs can't honor per-leaf carve-outs, so the params-side
    wire only engages when the quantization policy covers EVERY param leaf
    and no fp master copy is promised (the same static decision
    :func:`make_train_step` makes — see its ``full_quant``).  ``params``
    may be a concrete or abstract (ShapeDtypeStruct) tree.  When this is
    False under an engaged ZeRO + compressed-sync config, the updated
    params are gathered in fp32 by design.
    """
    if not (zero_opt_engaged(qcfg, mesh, data_axis)
            and wire_sync_engaged(qcfg, mesh, data_axis)):
        return False
    if qcfg.master_weights:
        return False
    pred = qcfg.policy.param_predicate()
    return all(pred(path, leaf) for path, leaf in
               jax.tree_util.tree_flatten_with_path(params)[0])


def zero_partitioner(qcfg: QuantConfig, params, n_shards: int):
    """The flat ZeRO-1 layout this config shards its optimizer state over.

    The plain :class:`~repro.dist.sharding.ZeroPartitioner` (minimal
    divisibility padding, leaf boundaries erased) unless the compressed
    sync runs a layout that must keep leaf boundaries — per-layer
    ``wire_grads`` groups or the overlapped bucketed wire — in which case
    the :class:`~repro.dist.sharding.GroupAlignedPartitioner` pads every
    leaf slot to the wire quantum so rank chunks and collective
    boundaries never straddle a leaf and per-leaf ⟨IL, FL⟩ survive the
    flatten.  With ``wire_overlap`` the aligned layout is additionally
    bucketed by :func:`repro.dist.overlap.plan_buckets` (same plan as the
    readiness taps) so each bucket is a contiguous aligned slot run.

    ``params`` may be concrete or abstract.  The decision is mesh-free on
    purpose: it must agree between :func:`zero_opt_state` (called at init,
    often before the mesh exists) and the step body, and every input to it
    is static config.
    """
    from repro.dist.sharding import (  # deferred: dist imports core
        GroupAlignedPartitioner, ZeroPartitioner)
    plan = qcfg.plan()
    groups = plan.spec("wire_grads").groups if "wire_grads" in plan else 0
    aligned = (qcfg.grad_allreduce_bits is not None
               and (groups > 0 or qcfg.wire_overlap))
    if not aligned:
        return ZeroPartitioner.create(params, n_shards)
    buckets = None
    if qcfg.wire_overlap:
        from repro.dist import overlap as overlap_lib
        sizes = tuple(int(math.prod(tuple(l.shape))) or 1
                      for l in jax.tree_util.tree_leaves(params))
        bplan = overlap_lib.plan_buckets(
            sizes, qcfg.wire_bucket_elems or overlap_lib.DEFAULT_BUCKET_ELEMS)
        # BucketPlan lists buckets in backward-ready (reverse flatten)
        # order; the partitioner wants flatten order.
        buckets = tuple(sorted(bplan.buckets, key=lambda r: r[0]))
    return GroupAlignedPartitioner.create(params, n_shards, buckets=buckets)


def zero_opt_state(optimizer, params, n_shards: int,
                   qcfg: Optional[QuantConfig] = None):
    """ZeRO-1 optimizer state: one flat padded vector per state tensor.

    Returns ``optimizer.init_shard`` over the flat ZeRO layout — a GLOBAL
    ``[padded_size]`` array per state leaf, meant to be placed with
    ``NamedSharding(mesh, P("data"))`` so each rank holds ``1/n_shards``
    of it (see ``launch.specs.train_state_shardings``).

    Pass the run's ``qcfg`` so the layout matches the step that will
    consume the state: per-layer wire formats and the overlapped wire run
    the group-aligned layout, whose padded size differs from the plain
    ZeroPartitioner's (see :func:`zero_partitioner`).  ``qcfg=None`` keeps
    the legacy plain layout.
    """
    from repro.dist.sharding import ZeroPartitioner  # deferred: dist imports core
    part = (zero_partitioner(qcfg, params, n_shards) if qcfg is not None
            else ZeroPartitioner.create(params, n_shards))
    flat = jax.eval_shape(lambda t: part.flatten(t), params)
    return optimizer.init_shard(flat)


def make_train_step(loss_fn, optimizer, qcfg: QuantConfig,
                    accum_steps: int = 1, mesh=None, data_axis: str = "data",
                    faults=None):
    """Build a quantized SGD/AdamW train step around ``loss_fn``.

    ``loss_fn(params, batch, qctx) -> (loss, aux)`` where ``aux`` is a dict
    that may contain ``"act_stats"`` (merged QuantStats from taps) and
    ``"dlogits_stats"`` (last-layer gradient stats, see models).  The
    returned step is pure: ``step(state, batch) -> (state, metrics)``.

    ``accum_steps > 1`` splits the global batch into microbatches scanned
    sequentially with fp32 gradient accumulation — the standard way to fit
    the large train cells in per-device HBM (activation memory scales with
    the microbatch, gradients are one extra params-sized buffer).

    ``qcfg.grad_allreduce_bits`` + ``mesh``: the forward/backward runs
    inside a ``shard_map`` over ``data_axis`` (params replicated, batch
    split) and parameter gradients are averaged by the int8-wire
    :func:`repro.dist.collectives.dps_allreduce_mean` — ~4× fewer gradient
    wire bytes than the implicit fp32 psum.  The wire ⟨IL, FL⟩ comes from
    the registry's dedicated ``wire_grads`` domain, and the dispatch-leg
    QuantStats feed that domain's controller (and only it — compute
    controllers never see wire events).  The path engages only on pure
    data-parallel meshes (every non-``data_axis`` mesh axis of size 1);
    tensor-parallel meshes fall back to the implicit psum with a warning.
    The restriction dates from a JAX 0.4 miscompile of partial-manual
    ``shard_map``; whether JAX 0.9's ``axis_names=`` form composes with
    the tensor-parallel rules is unverified.  On a single-device mesh (or
    ``mesh=None``) the path degrades to the identity all-reduce: the step
    is bit-identical to the uncompressed one.

    ``qcfg.zero_opt_shards`` + ``mesh``: ZeRO-1.  The optimizer state lives
    as flat ``P(data_axis)``-sharded slices of the ZeroPartitioner layout
    (1/n of the replicated bytes per device) and the optimizer steps one
    slice per rank inside the shard_map.  Without ``grad_allreduce_bits``
    the gradients come from the ordinary (implicit-psum) backward pass and
    the update legs are exact, so the step is **bit-exact** with the
    replicated one — fp32 state, ``clip_norm`` off (the cross-shard norm
    psum sums in a different order than the per-leaf norm), and optimizer
    scalars whose products are f32-exact (e.g. power-of-two
    lr/momentum/weight_decay; otherwise layout-dependent FMA contraction
    may drift the state by 1 ULP/step, see ``SGD._leaf``); with it, one
    fused shard_map body runs
    per-shard fwd/bwd → int8 ``dps_reduce_scatter_mean`` → local optimizer
    → int8 ``dps_allgather_params``, the grads-leg wire stats feed the
    ``wire_grads`` domain and the params-leg wire stats feed the
    ``wire_params`` domain.  Same pure-data-parallel constraint and
    single-device degradation as above.

    Per-layer wire formats (``wire_grads_groups > 0``) and the overlapped
    bucketed wire (``wire_overlap``) COMPOSE with ZeRO-1: the flat layout
    switches to the group-aligned partitioner (:func:`zero_partitioner`),
    whose aligned leaf slots keep per-leaf ⟨IL, FL⟩ through the flatten,
    and the fused body becomes readiness-tapped fwd/bwd → grouped int8
    ``zero_bucketed_reduce_scatter`` (one collective per bucket, backward-
    ready order) → local optimizer over aligned slices → grouped int8
    ``zero_allgather_params``.  At ``bits=None``-equivalent settings and
    under nearest rounding the decoded updates are bit-exact vs the
    replicated per-layer step (and under stochastic rounding too: every
    wire rounding-bit draw is keyed by global leaf index, see
    ``repro.dist.overlap``).  Mismatched ``zero_opt_shards`` vs the mesh
    warns and falls back to the replicated state — the same policy as
    every other engagement mismatch; only impossible configs raise.

    On a TPU with no mesh or a one-device mesh the weight snaps and the
    optimizer-input gradient quantization run the fused quantize kernel,
    one ``pallas_call`` per quantized leaf, its rounding bits from the
    core's PRNG (``train_step.fused_quant_active``); everywhere else they
    run the jnp path.  Both round by Eq. (2) on the same grid and feed the
    controllers the same stats; only the bit source differs.
    """
    plan = qcfg.plan()
    rounding = getattr(plan.controller("weights"), "rounding", qcfg.rounding)
    grad_domain = getattr(optimizer, "grad_domain", "grads")
    if grad_domain not in plan:
        raise ValueError(
            f"{type(optimizer).__name__}.grad_domain = {grad_domain!r} names "
            f"no precision domain in the plan ({plan.names}); the optimizer-"
            "input gradient quantization needs its format from the registry")

    wire_bits = qcfg.grad_allreduce_bits
    if wire_bits is not None and not 2 <= wire_bits <= 8:
        raise ValueError(f"grad_allreduce_bits={wire_bits}: the wire payload "
                         "is int8, so only 2..8 grid bits are supported")
    axis_sizes = _mesh_axis_sizes(mesh)
    n_data = int(axis_sizes.get(data_axis, 1))
    # Off TPU the interpreter's PRNG returns zeros (stochastic rounding
    # would floor), and under a multi-device mesh a pallas_call on a
    # sharded leaf would gather it: those steps keep the jnp tree passes.
    fused_quant = on_tpu() and (mesh is None or mesh.devices.size == 1)
    wire_sync = wire_bits is not None and n_data > 1
    if wire_sync and any(s > 1 for a, s in axis_sizes.items()
                         if a != data_axis):
        warnings.warn(
            "grad_allreduce_bits needs a pure data-parallel mesh (all "
            f"non-'{data_axis}' axes of size 1); got {axis_sizes}. Falling "
            "back to the implicit fp32 gradient all-reduce.")
        wire_sync = False

    # Engagement policy (uniform): a config/mesh MISMATCH — the requested
    # path simply cannot engage on this mesh — warns and falls back to the
    # equivalent uncompressed/replicated step; an IMPOSSIBLE config — one
    # no mesh could satisfy — raises.  The chosen paths are surfaced as
    # ``train_step.{wire_sync,zero_opt,wire_overlap,zero_groupaligned}_
    # active`` attributes.
    zero_opt = qcfg.zero_opt_shards is not None and n_data > 1
    if zero_opt and any(s > 1 for a, s in axis_sizes.items()
                        if a != data_axis):
        warnings.warn(
            "zero_opt_shards needs a pure data-parallel mesh (all "
            f"non-'{data_axis}' axes of size 1); got {axis_sizes}. Falling "
            "back to the replicated optimizer state.")
        zero_opt = False
    if zero_opt and qcfg.zero_opt_shards != n_data:
        warnings.warn(
            f"zero_opt_shards={qcfg.zero_opt_shards} does not match the "
            f"mesh's '{data_axis}' axis size ({n_data}); the optimizer "
            "state shards over that axis. Falling back to the replicated "
            "optimizer state.")
        zero_opt = False
    if zero_opt and not hasattr(optimizer, "update_shard"):
        raise TypeError(f"{type(optimizer).__name__} has no shard-local "
                        "update_shard/init_shard interface; ZeRO-1 needs it")
    if wire_sync and "wire_grads" not in plan:
        raise ValueError(
            "grad_allreduce_bits engages the compressed gradient sync but "
            f"the precision plan ({plan.names}) declares no 'wire_grads' "
            "domain to govern the wire format")
    wire_groups = plan.spec("wire_grads").groups if "wire_grads" in plan else 0
    if wire_sync and zero_opt and "wire_params" not in plan:
        raise ValueError(
            "zero_opt_shards + grad_allreduce_bits put the parameter "
            f"all-gather on the int8 wire, but the precision plan "
            f"({plan.names}) declares no 'wire_params' domain")
    wire_overlap = bool(qcfg.wire_overlap) and wire_sync
    # per-layer wire formats and the overlapped bucketed wire keep leaf
    # boundaries through the flatten via the group-aligned layout
    # (zero_partitioner); the sharded legs then run the grouped codec.
    zero_aligned = zero_opt and wire_sync and (wire_groups > 0
                                               or wire_overlap)
    if wire_sync or zero_opt:
        from repro.dist import collectives  # deferred: dist imports core
    if wire_overlap or zero_aligned:
        from repro.dist import overlap as overlap_lib
        bucket_elems = (qcfg.wire_bucket_elems
                        or overlap_lib.DEFAULT_BUCKET_ELEMS)

    # Health guards + fault injection (repro.resilience).  Both are
    # static decisions: guards/faults off leaves every body below — and
    # with it the compiled step — exactly as it was.  ``sig`` extends the
    # shard_map bodies with the extra signal plumbing (degrade flags in,
    # nonfinite count / sharded grad norm out).
    guards_on = qcfg.guards is not None
    if guards_on or faults is not None:
        from repro import resilience as rsl  # deferred: resilience imports core
    sig = guards_on or faults is not None
    wire_names = ()
    gidx = pidx = 0
    if guards_on:
        wire_names = rsl.wire_domains(plan)
        gidx = (wire_names.index("wire_grads")
                if "wire_grads" in wire_names else 0)
        pidx = (wire_names.index("wire_params")
                if "wire_params" in wire_names else 0)
    if (faults is not None and faults.wire_flip_at >= 0
            and not (wire_sync and not wire_overlap and not zero_opt)):
        raise ValueError(
            "FaultPlan.wire_flip_at targets the monolithic tree "
            "all-reduce payload; it needs an engaged compressed sync "
            "without wire_overlap or zero_opt_shards")

    def _grads(qparams, batch, fmts, k_a, microbatch_idx, tap=None):
        qctx = None
        if qcfg.enabled and qcfg.policy.quantizes("acts"):
            qctx = QCtx(acts_fmt=fmts["acts"], grads_fmt=fmts["grads"],
                        key=jax.random.fold_in(k_a, microbatch_idx),
                        rounding=rounding, collect_stats=True)
        # the readiness tap must sit INSIDE the differentiated function:
        # its custom-vjp backward tags each param leaf's cotangent at the
        # point the backward materializes it (repro.dist.overlap).
        fn = (loss_fn if tap is None
              else lambda p, b, c: loss_fn(tap(p), b, c))
        return jax.value_and_grad(fn, has_aux=True)(qparams, batch, qctx)

    def _accum_grads(qparams, batch, fmts, k_a, tap=None):
        if accum_steps == 1:
            return _grads(qparams, batch, fmts, k_a, 0, tap)
        micro = jax.tree.map(
            lambda x: x.reshape((accum_steps, x.shape[0] // accum_steps)
                                + x.shape[1:]), batch)

        def body(carry, xs):
            loss_acc, g_acc, stats_acc, idx = carry
            (loss, aux), g = _grads(qparams, xs, fmts, k_a, idx, tap)
            g_acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                 g_acc, g)
            stats_acc = stats_acc.merge(aux.get("act_stats",
                                                QuantStats.zero()))
            return (loss_acc + loss, g_acc, stats_acc, idx + 1), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), qparams)
        (loss, g, stats, _), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), g0, QuantStats.zero(),
                   jnp.zeros((), jnp.uint32)), micro,
            length=accum_steps)
        n = float(accum_steps)
        grads = jax.tree.map(lambda x, p: (x / n).astype(p.dtype), g, qparams)
        return (loss / n, {"act_stats": stats}), grads

    def _raw_grad_stats(grads, fmts, k_g, rank):
        """Compute-grid gradient stats measured on the RAW local gradients.

        In the wire-synced paths the optimizer-input ``quantize_grads``
        downstream sees gradients that already sit on the (coarser) wire
        grid, so its stats report near-zero error — fed to the grads
        controller they would starve it, ratchet the compute FL down, and
        coarsen the backward-tap grid until training destabilizes
        (observed on LeNet/MNIST-tiny).  The grads domain therefore
        consumes this stats-only measurement of the compute grid against
        the pre-wire gradients — the same quantization event the
        replicated path scores — while the gradient *values* flow through
        the wire untouched.
        """
        if not (qcfg.enabled and qcfg.policy.quantizes("grads")):
            return QuantStats.zero()
        _, st = quantize_grads(grads, fmts[grad_domain], qcfg,
                               jax.random.fold_in(k_g, rank))
        return st

    def _wire_synced_grads(qparams, batch, fmts, k_a, k_g, k_r,
                           deg_g=None, count=None):
        """Per-shard fwd/bwd + compressed gradient mean over ``data_axis``.

        Runs the whole gradient computation inside a full-manual
        ``shard_map``: each data shard sees its slice of the batch,
        computes local gradients, and the tree-wide
        ``dps_allreduce_mean`` replaces the implicit psum.  Scalars
        (loss, acc) come back pmean'ed and QuantStats psum'ed, so the
        caller sees the same global quantities as the GSPMD path.

        With ``wire_overlap`` the monolithic tree collective becomes the
        bucketed schedule (repro.dist.overlap): readiness taps on the
        params mark each bucket's gradients as the backward materializes
        them, and one compressed collective pair runs per bucket in that
        order — bit-exact vs the monolithic path under nearest rounding,
        identical dispatch-leg stats under both modes.

        Guards armed: ``deg_g`` (replicated i32 from last step's
        GuardState) selects between the int8 wire and a per-leaf fp32
        ``pmean`` fallback through ``lax.cond`` — the predicate is
        replicated, so every rank takes the same branch and the
        collectives inside stay congruent — and the body additionally
        returns the psum'ed nonfinite count of the RAW local gradients
        (the wire codec clips NaN silently, so detection must precede
        the encode).
        """
        def body(qparams, batch, fmts, k_a, k_g, k_r, *extra):
            deg_g = count = None
            if sig:
                deg_g, count = extra
            rank = jax.lax.axis_index(data_axis)
            tap = bplan = None
            if wire_overlap:
                bplan = overlap_lib.plan_buckets(
                    tuple(l.size
                          for l in jax.tree_util.tree_leaves(qparams)),
                    bucket_elems)
                tap = lambda p: overlap_lib.tap_params(p, bplan)
            (loss, aux), grads = _accum_grads(
                qparams, batch, fmts, jax.random.fold_in(k_a, rank), tap)
            if faults is not None:
                grads = rsl.apply_grad_faults(faults, grads, count)
            if wire_groups:
                n_leaves = len(jax.tree_util.tree_leaves(grads))
                if n_leaves != wire_groups:
                    raise ValueError(
                        f"wire_grads_groups={wire_groups} but the gradient "
                        f"tree has {n_leaves} leaves; per-layer wire formats "
                        "need one group per leaf (derive the config with "
                        "QuantConfig.with_per_layer_wire(params))")
            g_raw = _raw_grad_stats(grads, fmts, k_g, rank)
            bad = (jax.lax.psum(rsl.nonfinite_count(grads), data_axis)
                   if guards_on else None)

            def wire_leg(grads):
                if wire_overlap:
                    return overlap_lib.bucketed_allreduce_mean_tree(
                        grads, fmts, data_axis, k_r, mode=rounding,
                        domain="wire_grads", plan=bplan)
                if faults is not None:
                    return collectives.dps_allreduce_mean_tree(
                        grads, fmts, data_axis, k_r, mode=rounding,
                        domain="wire_grads",
                        payload_fault=rsl.payload_fault_fn(faults, count))
                return collectives.dps_allreduce_mean_tree(
                    grads, fmts, data_axis, k_r, mode=rounding,
                    domain="wire_grads")

            if guards_on:
                def f32_leg(grads):
                    # graceful degradation: exact per-leaf mean, zero wire
                    # stats (the guard must never feed from post-fallback
                    # values — see resilience.guards)
                    g = jax.tree.map(lambda x: jax.lax.pmean(x, data_axis),
                                     grads)
                    return g, QuantStats.zero(fmts["wire_grads"].il.shape)
                grads, wstats = jax.lax.cond(deg_g > 0, f32_leg, wire_leg,
                                             grads)
            else:
                grads, wstats = wire_leg(grads)
            wstats = collectives.psum_stats(wstats, data_axis)
            g_raw = collectives.psum_stats(g_raw, data_axis)
            loss = jax.lax.pmean(loss, data_axis)
            aux = {k: (collectives.psum_stats(v, data_axis)
                       if isinstance(v, QuantStats)
                       else jax.lax.pmean(v, data_axis))
                   for k, v in aux.items()}
            out = ((loss, aux), grads, wstats, g_raw)
            return out + (bad,) if guards_on else out

        n_in = 8 if sig else 6
        n_out = 5 if guards_on else 4
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(data_axis)) + (P(),) * (n_in - 2),
            out_specs=(P(),) * n_out, check_vma=False)
        args = (qparams, batch, fmts, k_a, k_g, k_r)
        if sig:
            args += (deg_g, count)
        return fn(*args)

    def _zero_wire_step(part, full_quant, qparams, pflat, opt_state, batch,
                        fmts, count, k_a, k_g, k_r, deg_g=None, deg_p=None):
        """Fused ZeRO-1 step body: per-shard fwd/bwd, int8 reduce-scatter of
        the flat gradients, shard-local optimizer, all-gather of the
        updated parameter shards.

        ``full_quant`` (static) says every param leaf passes the policy's
        ``param_predicate``: the flat layout erases leaf identity, so the
        params all-gather rides the int8 wire — and the optimizer-input
        gradient quantization applies to the flat slice — only when no
        leaf is policy-excluded and no fp master copy is promised;
        otherwise the params leg gathers fp32 (gradient wire compression
        still applies to every leaf, exactly like ``dps_allreduce_mean``).

        Returns ``((loss, aux), new_flat_params, new_opt_state, g_wire,
        p_wire, g_stats)`` where ``g_wire``/``p_wire`` are the psum'ed
        QuantStats of the two wire legs (gradients / parameters) and
        ``g_stats`` the compute-grid gradient stats measured on the raw
        local gradients (see ``_raw_grad_stats``).

        Guards armed: ``deg_g``/``deg_p`` select — per wire domain,
        through ``lax.cond`` on the replicated flags — the fp32 fallback
        for the matching leg: an exact ``psum_scatter``/n of the flat
        gradients (same rank-major chunk order as ``part.shard``) and
        the fp32 tiled all-gather; the body additionally returns the
        psum'ed raw-gradient nonfinite count and the global squared norm
        of the decoded gradient shards (the spike detector's input).
        """
        def body(qparams, pflat, opt_local, batch, fmts, count, k_a, k_g,
                 k_r, *extra):
            deg_g = deg_p = None
            if sig:
                deg_g, deg_p = extra
            rank = jax.lax.axis_index(data_axis)
            k1, k2 = jax.random.split(k_r)
            (loss, aux), grads = _accum_grads(
                qparams, batch, fmts, jax.random.fold_in(k_a, rank))
            if faults is not None:
                grads = rsl.apply_grad_faults(faults, grads, count)
            g_stats = _raw_grad_stats(grads, fmts, k_g, rank)
            bad = (jax.lax.psum(rsl.nonfinite_count(grads), data_axis)
                   if guards_on else None)
            gflat = part.flatten(grads)

            def wire_rs(gflat):
                return collectives.dps_reduce_scatter_mean(
                    gflat, fmts, data_axis, k1, mode=rounding,
                    domain="wire_grads")

            if guards_on:
                def f32_rs(gflat):
                    sc = jax.lax.psum_scatter(gflat, data_axis,
                                              scatter_dimension=0,
                                              tiled=True)
                    return (sc / n_data,
                            QuantStats.zero(fmts["wire_grads"].il.shape))
                gshard, g_wire = jax.lax.cond(deg_g > 0, f32_rs, wire_rs,
                                              gflat)
            else:
                gshard, g_wire = wire_rs(gflat)
            if full_quant and qcfg.enabled and qcfg.policy.quantizes("grads"):
                # optimizer-input gradient quantization (Alg. 1), on this
                # rank's slice with the step's own rounding mode (matching
                # the replicated quantize_grads); stats-wise the event is
                # degenerate — the shard already sits on the wire grid —
                # so the controller stream is g_stats above, not this.
                gshard, _ = fxp.quantize(
                    gshard, fmts[grad_domain], mode=qcfg.rounding,
                    key=jax.random.fold_in(k_g, 0x524157 + rank))
            g2 = (jax.lax.psum(jnp.sum(jnp.square(
                gshard.astype(jnp.float32))), data_axis)
                if guards_on else None)
            pshard = part.shard(pflat, rank)
            upd, new_opt = optimizer.update_shard(gshard, opt_local, pshard,
                                                  count, axis_name=data_axis)
            if full_quant:
                def wire_ag(x):
                    return collectives.dps_allgather_params(
                        x, fmts, data_axis, k2, mode=rounding,
                        domain="wire_params")
                if guards_on:
                    def f32_ag(x):
                        return (jax.lax.all_gather(x, data_axis, axis=0,
                                                   tiled=True),
                                QuantStats.zero(
                                    fmts["wire_params"].il.shape))
                    new_flat, p_wire = jax.lax.cond(deg_p > 0, f32_ag,
                                                    wire_ag, pshard + upd)
                else:
                    new_flat, p_wire = wire_ag(pshard + upd)
            else:
                new_flat = jax.lax.all_gather(pshard + upd, data_axis,
                                              axis=0, tiled=True)
                p_wire = QuantStats.zero()
            g_wire = collectives.psum_stats(g_wire, data_axis)
            p_wire = collectives.psum_stats(p_wire, data_axis)
            g_stats = collectives.psum_stats(g_stats, data_axis)
            loss = jax.lax.pmean(loss, data_axis)
            aux = {k: (collectives.psum_stats(v, data_axis)
                       if isinstance(v, QuantStats)
                       else jax.lax.pmean(v, data_axis))
                   for k, v in aux.items()}
            out = ((loss, aux), new_flat, new_opt, g_wire, p_wire, g_stats)
            return out + (bad, g2) if guards_on else out

        n_in = 11 if sig else 9
        base_out = ((P(), P()), P(), P(data_axis), P(), P(), P())
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), P(data_axis), P(data_axis), P(), P(), P(),
                      P(), P()) + (P(),) * (n_in - 9),
            out_specs=base_out + ((P(), P()) if guards_on else ()),
            check_vma=False)
        args = (qparams, pflat, opt_state, batch, fmts, count, k_a, k_g,
                k_r)
        if sig:
            args += (deg_g, deg_p)
        return fn(*args)

    def _zero_aligned_wire_step(part, full_quant, qparams, pflat, opt_state,
                                batch, fmts, count, k_a, k_g, k_r,
                                deg_g=None, deg_p=None):
        """Group-aligned fused ZeRO-1 step: per-shard fwd/bwd, grouped int8
        reduce-scatter per bucket (backward-ready order when the overlap
        engages), shard-local optimizer over aligned slices, grouped int8
        (or fp32) all-gather of the updated parameter shards.

        The sharded twin of ``_zero_wire_step`` for the
        GroupAlignedPartitioner layout: per-leaf ⟨IL, FL⟩ from the [G]
        ``wire_grads``/``wire_params`` tables ride both legs, and with
        ``wire_overlap`` the gradients carry readiness taps so each
        bucket's reduce-scatter dispatches as the backward materializes
        it.  Same return contract as ``_zero_wire_step``, including the
        guard extensions (``deg_g``/``deg_p`` fallback conds, raw
        nonfinite count, sharded grad-norm signal).
        """
        def body(qparams, pflat, opt_local, batch, fmts, count, k_a, k_g,
                 k_r, *extra):
            deg_g = deg_p = None
            if sig:
                deg_g, deg_p = extra
            rank = jax.lax.axis_index(data_axis)
            tap = None
            if wire_overlap:
                bplan = overlap_lib.plan_buckets(
                    tuple(l.size
                          for l in jax.tree_util.tree_leaves(qparams)),
                    bucket_elems)
                tap = lambda p: overlap_lib.tap_params(p, bplan)
            (loss, aux), grads = _accum_grads(
                qparams, batch, fmts, jax.random.fold_in(k_a, rank), tap)
            if faults is not None:
                grads = rsl.apply_grad_faults(faults, grads, count)
            if wire_groups:
                n_leaves = len(jax.tree_util.tree_leaves(grads))
                if n_leaves != wire_groups:
                    raise ValueError(
                        f"wire_grads_groups={wire_groups} but the gradient "
                        f"tree has {n_leaves} leaves; per-layer wire formats "
                        "need one group per leaf (derive the config with "
                        "QuantConfig.with_per_layer_wire(params))")
            g_stats = _raw_grad_stats(grads, fmts, k_g, rank)
            bad = (jax.lax.psum(rsl.nonfinite_count(grads), data_axis)
                   if guards_on else None)

            # k_r goes to BOTH legs verbatim — the same key the replicated
            # tree collective consumes, so every leg-1 draw (split(fold_in(
            # k_r, idx))) and leg-2 draw (fold_in(k_r, LEG2)) matches the
            # replicated per-layer step bit for bit; the params leg derives
            # its own disjoint stream (fold_in(k_r, WPLG)) internally.
            def wire_rs(grads):
                return overlap_lib.zero_bucketed_reduce_scatter(
                    grads, fmts, data_axis, k_r, part=part, mode=rounding,
                    domain="wire_grads", tag_buckets=wire_overlap)

            if guards_on:
                def f32_rs(grads):
                    # exact fallback over the same aligned flat layout:
                    # psum_scatter's rank-major chunks match part.shard
                    sc = jax.lax.psum_scatter(part.flatten(grads),
                                              data_axis,
                                              scatter_dimension=0,
                                              tiled=True)
                    return (sc / n_data,
                            QuantStats.zero(fmts["wire_grads"].il.shape))
                gshard, g_wire = jax.lax.cond(deg_g > 0, f32_rs, wire_rs,
                                              grads)
            else:
                gshard, g_wire = wire_rs(grads)
            if full_quant and qcfg.enabled and qcfg.policy.quantizes("grads"):
                # optimizer-input gradient quantization on this rank's
                # slice (same contract as _zero_wire_step)
                gshard, _ = fxp.quantize(
                    gshard, fmts[grad_domain], mode=qcfg.rounding,
                    key=jax.random.fold_in(k_g, 0x524157 + rank))
            g2 = (jax.lax.psum(jnp.sum(jnp.square(
                gshard.astype(jnp.float32))), data_axis)
                if guards_on else None)
            pshard = part.shard(pflat, rank)
            upd, new_opt = optimizer.update_shard(gshard, opt_local, pshard,
                                                  count, axis_name=data_axis)

            def f32_gather(x):
                # fp32 return leg; the aligned layout is bucket-major, so
                # the rank-major gather goes through part.assemble
                gathered = jax.lax.all_gather(x, data_axis, axis=0,
                                              tiled=False)
                return (part.assemble(gathered),
                        QuantStats.zero(fmts["wire_params"].il.shape))

            if full_quant:
                def wire_ag(x):
                    return overlap_lib.zero_allgather_params(
                        x, fmts, data_axis, k_r, part=part,
                        mode=rounding, domain="wire_params")
                if guards_on:
                    new_flat, p_wire = jax.lax.cond(deg_p > 0, f32_gather,
                                                    wire_ag, pshard + upd)
                else:
                    new_flat, p_wire = wire_ag(pshard + upd)
            else:
                new_flat, p_wire = f32_gather(pshard + upd)
            g_wire = collectives.psum_stats(g_wire, data_axis)
            p_wire = collectives.psum_stats(p_wire, data_axis)
            g_stats = collectives.psum_stats(g_stats, data_axis)
            loss = jax.lax.pmean(loss, data_axis)
            aux = {k: (collectives.psum_stats(v, data_axis)
                       if isinstance(v, QuantStats)
                       else jax.lax.pmean(v, data_axis))
                   for k, v in aux.items()}
            out = ((loss, aux), new_flat, new_opt, g_wire, p_wire, g_stats)
            return out + (bad, g2) if guards_on else out

        n_in = 11 if sig else 9
        base_out = ((P(), P()), P(), P(data_axis), P(), P(), P())
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), P(data_axis), P(data_axis), P(), P(), P(),
                      P(), P()) + (P(),) * (n_in - 9),
            out_specs=base_out + ((P(), P()) if guards_on else ()),
            check_vma=False)
        args = (qparams, pflat, opt_state, batch, fmts, count, k_a, k_g,
                k_r)
        if sig:
            args += (deg_g, deg_p)
        return fn(*args)

    def _zero_plain_opt(part, gflat, pflat, opt_state, count):
        """ZeRO-1 optimizer leg without wire compression: slice the (already
        averaged, replicated) flat gradients, step the local shard, and
        all-gather the updated parameter shards in fp32.  Every leg is an
        exact copy, so the reassembled parameters are bit-identical to the
        replicated optimizer step whenever the shard-local optimizer math
        is (see ``make_train_step``'s ZeRO note on FMA contraction)."""
        def body(gflat, pflat, opt_local, count):
            rank = jax.lax.axis_index(data_axis)
            upd, new_opt = optimizer.update_shard(
                part.shard(gflat, rank), opt_local, part.shard(pflat, rank),
                count, axis_name=data_axis)
            new_flat = jax.lax.all_gather(part.shard(pflat, rank) + upd,
                                          data_axis, axis=0, tiled=True)
            return new_flat, new_opt

        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(), P(), P(data_axis), P()),
                           out_specs=(P(), P(data_axis)), check_vma=False)
        return fn(gflat, pflat, opt_state, count)

    def train_step(state: TrainState, batch):
        key = jax.random.fold_in(state.rng, state.step)
        k_w, k_g, k_a = jax.random.split(key, 3)
        fmts = bundle_formats(qcfg, state.dps)

        # -- forward/backward in the quantized regime (Alg. 1 lines 9-20) --
        qparams, w_stats = quantize_params(state.params, fmts["weights"],
                                           qcfg, k_w, fused_quant)
        g_wire = p_wire = wire_stats = None
        bad_count = gnorm = None
        deg_g = deg_p = jnp.zeros((), jnp.int32)
        if guards_on:
            if state.guard is None:
                raise ValueError(
                    "qcfg.guards is armed but TrainState.guard is None; "
                    "build the state with TrainState.create(..., qcfg, ...) "
                    "or restore with qtrain.guard_restore_defaults")
            # LAST step's degradation flags drive THIS step's collective
            # branch — a traced input, so fallback and wire live in the
            # same compiled step (no recompile at the trip boundary).
            if wire_names:
                deg_g = state.guard.degraded[gidx]
                if "wire_params" in wire_names:
                    deg_p = state.guard.degraded[pidx]
        if zero_opt:
            # ZeRO-1: the optimizer steps flat P(data)-sharded slices of the
            # flat layout (plain or group-aligned, see zero_partitioner),
            # then the updated parameter shards are gathered back into the
            # (replicated) tree.
            part = zero_partitioner(qcfg, state.params, n_data)
            pflat = part.flatten(state.params)
            if wire_sync:
                # the flat wire legs can't honor per-leaf carve-outs: only
                # engage them on the params/optimizer side when the policy
                # would quantize every leaf anyway and no fp master copy
                # is promised (static decision, uniform across steps).
                full_quant = wire_params_engaged(qcfg, state.params, mesh,
                                                 data_axis)
                if not full_quant:
                    warnings.warn(
                        "zero_opt_shards + grad_allreduce_bits: the policy "
                        "excludes some param leaves (or master_weights is "
                        "set), and the flat ZeRO layout cannot skip them "
                        "per-leaf — gathering updated params in fp32 and "
                        "skipping the flat optimizer-input gradient "
                        "quantization (the gradient wire stays int8).")
                k_r = jax.random.fold_in(key, 0x57495245)  # "WIRE"
                step_fn = (_zero_aligned_wire_step if zero_aligned
                           else _zero_wire_step)
                res = step_fn(part, full_quant, qparams, pflat,
                              state.opt_state, batch, fmts, state.step,
                              k_a, k_g, k_r,
                              *((deg_g, deg_p) if sig else ()))
                (loss, aux), new_flat, opt_state, g_wire, p_wire, g_stats \
                    = res[:6]
                if guards_on:
                    bad_count, g2 = res[6:]
                    gnorm = jnp.sqrt(g2)
                wire_stats = g_wire.merge(p_wire)
            else:
                # exact legs: grads from the ordinary (implicit-psum)
                # backward pass, slice + step + fp32 gather — bit-exact
                # with the replicated optimizer step.
                (loss, aux), grads = _accum_grads(qparams, batch, fmts, k_a)
                if faults is not None:
                    grads = rsl.apply_grad_faults(faults, grads, state.step)
                if guards_on:
                    bad_count = rsl.nonfinite_count(grads)
                    gnorm = rsl.global_norm(grads)
                grads, g_stats = quantize_grads(grads, fmts[grad_domain],
                                                qcfg, k_g)
                new_flat, opt_state = _zero_plain_opt(
                    part, part.flatten(grads), pflat, state.opt_state,
                    state.step)
            new_params = part.unflatten(new_flat)
        else:
            if wire_sync:
                # the wire path derives its own RNG stream instead of
                # widening the step's key split, so the default path stays
                # bit-identical to a step built without a mesh.
                k_r = jax.random.fold_in(key, 0x57495245)  # "WIRE"
                res = _wire_synced_grads(
                    qparams, batch, fmts, k_a, k_g, k_r,
                    *((deg_g, state.step) if sig else ()))
                if guards_on:
                    (loss, aux), grads, wire_stats, g_raw, bad_count = res
                    # spike detection reads the DECODED mean — transport
                    # corruption (a flipped payload) only exists there
                    gnorm = rsl.global_norm(grads)
                else:
                    (loss, aux), grads, wire_stats, g_raw = res
                # the optimizer-input snap still applies (Alg. 1), but the
                # controller stream is the raw-gradient measurement — the
                # mean already sits on the wire grid, so this event's own
                # stats are degenerate (see _raw_grad_stats).
                grads, _ = quantize_grads(grads, fmts[grad_domain], qcfg,
                                          k_g)
                g_stats = g_raw
            else:
                (loss, aux), grads = _accum_grads(qparams, batch, fmts, k_a)
                if faults is not None:
                    grads = rsl.apply_grad_faults(faults, grads, state.step)
                if guards_on:
                    bad_count = rsl.nonfinite_count(grads)
                    gnorm = rsl.global_norm(grads)
                grads, g_stats = quantize_grads(grads, fmts[grad_domain],
                                                qcfg, k_g, fused_quant)
            # -- update (Alg. 1 line 18) --
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params,
                                                  count=state.step)
            with jax.named_scope("optim"):
                new_params = jax.tree.map(lambda p, u: p + u, state.params,
                                          updates)

        if "dlogits_stats" in aux and qcfg.stat_scope == "last_layer":
            g_stats = aux["dlogits_stats"]
        elif "dlogits_stats" in aux:
            g_stats = g_stats.merge(aux["dlogits_stats"])
        if qcfg.stat_scope == "last_layer" and "last_act_stats" in aux:
            a_stats = aux["last_act_stats"]
        else:
            a_stats = aux.get("act_stats", QuantStats.zero())

        # -- re-snap weights to the grid (Alg. 1 line 19) --
        if (qcfg.enabled and qcfg.policy.quantizes("weights")
                and not qcfg.master_weights):
            new_params, w_stats2 = quantize_params(
                new_params, fmts["weights"], qcfg, jax.random.fold_in(k_w, 1),
                fused_quant)
            w_stats = w_stats.merge(w_stats2)

        # -- scale_precision (Alg. 2, one controller per domain) --
        # Each wire leg feeds its own wire domain, never a compute
        # controller: a clipped wire element must move the *wire* radix,
        # not ratchet the compute IL (see module docstring).
        streams = {"weights": w_stats, "acts": a_stats, "grads": g_stats}
        if wire_stats is not None:
            if zero_opt:
                streams["wire_grads"] = g_wire
                streams["wire_params"] = p_wire
            else:
                streams["wire_grads"] = wire_stats
        new_dps = update_dps_bundle(qcfg, state.dps, streams, {"loss": loss})

        # -- health guards: fold this step's signals, gate the update --
        new_guard = state.guard
        if guards_on:
            wire_legs = {}
            if wire_stats is not None:
                wire_legs = ({"wire_grads": g_wire, "wire_params": p_wire}
                             if zero_opt else {"wire_grads": wire_stats})
            new_guard, g_ok, trip_any = rsl.update_guard(
                qcfg.guards, plan, state.guard, loss=loss,
                grads_bad=bad_count, gnorm=gnorm,
                wire_ov=rsl.guards.domain_overflow(plan, wire_legs),
                new_dps=new_dps, grads_domain_idx=gidx)
            # the skip gate: a poisoned step must not reach the params,
            # optimizer state, or controllers.  jnp.where is an exact
            # select, so with g_ok True (no fault) every value passes
            # through bit-identical — the guard-transparency contract.
            keep = lambda new, old: jax.tree.map(
                lambda a, b: jnp.where(g_ok, a, b), new, old)
            new_params = keep(new_params, state.params)
            opt_state = keep(opt_state, state.opt_state)
            new_dps = keep(new_dps, state.dps)
            if qcfg.guards.widen_on_trip:
                # reactive headroom: one extra IL bit on the compute
                # grads domain the step a trip fires (dps._clamp_fmt
                # keeps caps and the exactness span)
                new_dps = rsl.widen_on_trip(plan, new_dps, trip_any)

        # -- telemetry: ⟨IL, FL⟩ + E/R per domain (scalarized for [G];
        # grouped domains also report the per-group spread so per-layer
        # wire formats are visible in the train log) --
        short = {"weights": "w", "acts": "a", "grads": "g"}
        metrics = {"loss": loss}
        for name, spec in plan.domains:
            fmt, tag = fmts[name], short.get(name, name)
            scalar = (lambda x: x) if not spec.groups else jnp.mean
            metrics[f"il_{tag}"] = scalar(fmt.il)
            metrics[f"fl_{tag}"] = scalar(fmt.fl)
            if spec.groups:
                metrics[f"il_{tag}_min"] = jnp.min(fmt.il)
                metrics[f"il_{tag}_max"] = jnp.max(fmt.il)
                metrics[f"fl_{tag}_min"] = jnp.min(fmt.fl)
                metrics[f"fl_{tag}_max"] = jnp.max(fmt.fl)
            st = streams.get(spec.stream(name))
            if st is not None:
                metrics[f"E_{tag}"] = scalar(st.quant_error())
                metrics[f"R_{tag}"] = scalar(st.overflow_rate())
        if wire_stats is not None:
            ws = wire_stats
            if ws.count.ndim:          # [G] per-layer stats -> global view
                ws = QuantStats(*(jnp.sum(f) for f in
                                  (ws.count, ws.nonzero, ws.overflow,
                                   ws.abs_err_sum, ws.rel_err_sum,
                                   ws.abs_sum)),
                                max_abs=jnp.max(ws.max_abs))
            metrics["E_wire"] = ws.quant_error()
            metrics["R_wire"] = ws.overflow_rate()
        if guards_on:
            # the health word + counters ride the ordinary metrics dict,
            # so they drain at the driver's log points with everything
            # else — no extra host sync (the PR 7 deferred-fetch pattern)
            metrics["health"] = new_guard.health
            metrics["skipped"] = new_guard.skipped
            metrics["trips"] = new_guard.trips
            metrics["degraded"] = (jnp.max(new_guard.degraded)
                                   if wire_names
                                   else jnp.zeros((), jnp.int32))
        new_state = TrainState(
            step=state.step + 1, params=new_params, opt_state=opt_state,
            dps=new_dps, rng=state.rng, last_loss=loss.astype(jnp.float32),
            guard=new_guard)
        return new_state, metrics

    # introspection for callers and tests: did the compressed paths and the
    # fused tree quantization engage?
    train_step.wire_sync_active = wire_sync
    train_step.zero_opt_active = zero_opt
    train_step.wire_overlap_active = wire_overlap
    train_step.zero_groupaligned_active = zero_aligned
    train_step.guards_active = guards_on
    train_step.fused_quant_active = fused_quant
    return train_step
