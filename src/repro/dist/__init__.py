"""Distribution subsystem: logical sharding rules + compressed collectives.

Sharding (``repro.dist.sharding``)
----------------------------------
Binds a mesh and :class:`LogicalRules` into a context so model code can
express placement as *logical* axis names ("batch", "tp", "fsdp", ...)
that resolve against whatever mesh the run builds — or no-op entirely on
a single device.

The int8 wire format (``repro.dist.collectives``)
-------------------------------------------------
Gradient payloads travel the interconnect as **grid integers**: a value
``x`` quantized onto the paper's ⟨IL, FL⟩ fixed-point grid is shipped as
``round(x · 2^FL)`` in one int8 byte (IL + FL ≤ 8 keeps every grid
integer in [-128, 127]; statically wider formats are rejected eagerly,
traced ones saturate with the clipped count folded into
``QuantStats.overflow``).  The receiver decodes with ``wire · 2^-FL``.

:func:`dps_allreduce_mean` is the collective built on that codec: a
reduce-scatter (tiled ``all_to_all``) plus ``all_gather``, **both legs
int8** — ≈ 2·|x| wire bytes against ≈ 8·|x| for an fp32 ring all-reduce.
Stochastic rounding keeps each leg unbiased and under one grid step of
error, so the result lands within **two grid steps (2·2^-FL)** of the
exact mean.  Encoding runs through the fused Pallas ``dps_quant_wire``
kernel on TPU (one read-x/write-wire HBM pass, stats in SMEM) and plain
jnp ops elsewhere; formats may be per-group (⟨IL, FL⟩ of shape [G] over
contiguous chunks of the flattened tensor).

:func:`dps_reduce_scatter_mean` / :func:`dps_allgather_params` split the
same schedule into ZeRO-1's two halves: the scatter leg leaves the mean
**sharded** (one flat chunk per rank, the
:class:`~repro.dist.sharding.ZeroPartitioner` padded layout) so each rank
steps its slice of the optimizer locally, and the gather leg ships the
updated parameter shards back — both int8.  See ``dist/README.md`` for
when each schedule engages.

Training integration — ``QuantConfig.grad_allreduce_bits``
----------------------------------------------------------
The knob that turns the codec into the gradient hot path::

    from repro.core import qtrain
    from repro.dist import make_mesh
    from repro.optim import SGDConfig, make_optimizer

    mesh = make_mesh((jax.device_count(),), ("data",))
    qcfg = qtrain.QuantConfig(grad_allreduce_bits=8)
    step = qtrain.make_train_step(loss_fn, make_optimizer(SGDConfig()),
                                  qcfg, mesh=mesh)
    state, metrics = jax.jit(step)(state, batch)   # metrics["E_wire"], ...

The forward/backward runs per data shard under ``shard_map`` and the
parameter-gradient mean is computed by :func:`dps_allreduce_mean` with
the ⟨IL, FL⟩ of the registry's dedicated **wire_grads** precision domain
(every collective leg picks its own domain's format out of the
``qtrain.bundle_formats`` mapping — see :func:`resolve_domain_format`).
The dispatch-leg :class:`QuantStats` feed that wire domain's controller
(default "flexpoint": max-abs-driven radix placement), so wire clipping
moves the *wire* radix rather than ratcheting the compute controllers'
IL — the instability the registry redesign fixed, see dist/README.md.
Single-device meshes degrade to the identity all-reduce; the CLI
spelling is ``repro.launch.train --grad-allreduce-bits 8``.
"""

from repro.dist.sharding import (LogicalRules, ZeroPartitioner, axis_rules,
                                 current_mesh_rules, logical_constraint,
                                 make_mesh, model_axis_size, tree_specs)
from repro.dist.collectives import (dps_allgather_params, dps_allreduce_mean,
                                    dps_allreduce_mean_tree,
                                    dps_reduce_scatter_mean, psum_stats,
                                    resolve_domain_format, wire_decode,
                                    wire_encode, wire_format)

__all__ = [
    "LogicalRules", "ZeroPartitioner", "axis_rules", "current_mesh_rules",
    "logical_constraint", "make_mesh", "model_axis_size", "tree_specs",
    "dps_allgather_params", "dps_allreduce_mean", "dps_allreduce_mean_tree",
    "dps_reduce_scatter_mean", "psum_stats", "resolve_domain_format",
    "wire_decode", "wire_encode", "wire_format",
]
