"""Every Pallas kernel of the main path compiles for a TPU v5e.

The TPU compiler is installed beside JAX, so each kernel is compiled here
for a described (not attached) ``v5e:2x2`` chip at llama3_2_3b widths and
must come out as a Mosaic ``tpu_custom_call``.  Interpret-mode tests cannot
see what this catches: scalar bit-casts, unsigned-to-float casts and
contraction layouts that Mosaic refuses.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around these compiles,
since what they would write cannot be read back without a chip.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dps_quant as dq
from repro.kernels import ops
from repro.kernels import paged_attn as pa

D_MODEL, N_HEADS, N_KV, HEAD_DIM, D_FF = 3072, 24, 8, 128, 8192

# one layer's attention weights as a group-aligned wire buffer:
# wq, wk, wv, wo (every size a multiple of the 4096-element quantum)
GROUP_SIZES = (D_MODEL * D_MODEL, D_MODEL * N_KV * HEAD_DIM,
               D_MODEL * N_KV * HEAD_DIM, D_MODEL * D_MODEL)
QUANTUM = dq.DEFAULT_GROUP_QUANTUM

# (stochastic, use_onchip_prng): nearest, stochastic with the bits
# operand, stochastic with the on-chip PRNG
ROUNDINGS = [(False, False), (True, False), (True, True)]
ROUNDING_IDS = ["nearest", "stochastic-bits", "stochastic-prng"]


@pytest.fixture(scope="module")
def chip():
    """A sharding on one described v5e chip, with the compile cache off."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("wire", [False, True], ids=["quant", "wire"])
@pytest.mark.parametrize("stochastic, prng", ROUNDINGS, ids=ROUNDING_IDS)
def test_global_quant_kernel_compiles(chip, wire, stochastic, prng):
    kernel = dq.dps_quant_wire_pallas if wire else dq.dps_quant_pallas
    fn = functools.partial(kernel, stochastic=stochastic,
                           use_onchip_prng=prng, interpret=False)
    w = (D_MODEL, D_FF)                       # an MLP weight
    txt = _compile_text(fn, chip, (w, jnp.float32), ((3,), jnp.int32),
                        (w, jnp.uint32), (w, jnp.float32))
    assert "tpu_custom_call" in txt


# the benchmark's bf16 leaves (an MLP weight, the vocab-padded head), and
# a ragged f32 leaf whose last blocks hang past both edges
LEAF_SHAPES = [((6144, 16384), jnp.bfloat16), ((6144, 11776), jnp.bfloat16),
               ((1157, 600), jnp.float32)]


@pytest.mark.parametrize("shape, dtype", LEAF_SHAPES,
                         ids=["mlp", "head", "ragged"])
@pytest.mark.parametrize("stochastic, prng", ROUNDINGS, ids=ROUNDING_IDS)
def test_leaf_quant_kernel_compiles(chip, shape, dtype, stochastic, prng):
    fn = functools.partial(dq.dps_quant_leaf_pallas, stochastic=stochastic,
                           use_onchip_prng=prng, interpret=False)
    args = [(shape, dtype), ((3,), jnp.int32)]
    if stochastic and not prng:
        args.append((shape, jnp.uint32))
    txt = _compile_text(fn, chip, *args)
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("stochastic, prng", ROUNDINGS, ids=ROUNDING_IDS)
def test_group_wire_kernel_compiles(chip, stochastic, prng):
    n = sum(GROUP_SIZES)
    tiles = n // QUANTUM
    fn = functools.partial(dq.dps_quant_group_wire_pallas,
                           stochastic=stochastic, use_onchip_prng=prng,
                           quantum=QUANTUM, interpret=False)
    txt = _compile_text(fn, chip, ((n,), jnp.float32),
                        ((len(GROUP_SIZES), 2), jnp.int32),
                        ((tiles,), jnp.int32), ((1,), jnp.int32),
                        ((n,), jnp.uint32), ((n,), jnp.float32))
    assert "tpu_custom_call" in txt


def test_wire_reduce_kernel_compiles(chip):
    n_ranks = 4
    chunk = sum(GROUP_SIZES) // n_ranks
    fn = functools.partial(dq.dps_wire_reduce_pallas, quantum=QUANTUM,
                           interpret=False)
    txt = _compile_text(fn, chip, ((n_ranks, chunk), jnp.int8),
                        ((len(GROUP_SIZES), 2), jnp.int32),
                        ((chunk // QUANTUM,), jnp.int32))
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("page_size", [4, 16])
def test_paged_attn_kernel_compiles(chip, page_size):
    slots, pages_per_seq, n_pages = 8, 37, 297
    fn = functools.partial(pa.paged_attn_pallas, scale=HEAD_DIM ** -0.5,
                           interpret=False)
    pool = ((n_pages, page_size, N_KV, HEAD_DIM), jnp.int8)
    txt = _compile_text(fn, chip, ((slots, N_HEADS, HEAD_DIM), jnp.float32),
                        pool, pool, ((n_pages, 2), jnp.int32),
                        ((slots, pages_per_seq), jnp.int32),
                        ((slots,), jnp.int32))
    assert "tpu_custom_call" in txt


# A small DPS config of the benchmark's model family (InternVL2's language
# model at cut widths, bf16 weights).  At these sizes XLA prefetches some
# leaves into VMEM ahead of their kernel, a copy that keeps shape and
# layout; at the benchmark's sizes the leaves stay in HBM.
def _small_vlm():
    import dataclasses

    from repro.configs.internvl2_26b import CONFIG
    return dataclasses.replace(
        CONFIG, n_layers=2, d_model=2048, n_heads=16, n_kv_heads=8,
        head_dim=128, d_ff=4096, vocab=4096, n_patches=16, train_accum=1)


_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OPERANDS = re.compile(r"custom-call\(([^)]*)\)")
_OPCODE = re.compile(r"(?<![\w\-.%])([a-z][a-z0-9\-]*)\(")


def test_train_step_feeds_each_fused_kernel_its_leaf(chip, monkeypatch):
    """On a TPU the DPS train step quantizes the weight and gradient trees
    with one fused kernel per quantized leaf and event (snap, gradient,
    re-snap), and feeds each its leaf as it lies: no bits or mask operand,
    no copy, pad or relayout of the leaf."""
    from repro.configs.base import ShapeConfig
    from repro.core import qtrain
    from repro.launch import specs
    from repro.models import registry
    from repro.models.common import abstract_params
    from repro.optim import AdamWConfig, make_optimizer

    # the step decides from the platform, which here is the CPU
    monkeypatch.setattr(qtrain, "on_tpu", lambda: True)
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    cfg = _small_vlm()
    qcfg = qtrain.QuantConfig()
    opt = make_optimizer(AdamWConfig())
    step = specs.build_train_step(cfg, qcfg, opt)
    assert step.fused_quant_active
    state = specs.abstract_train_state(cfg, opt, qcfg)
    batch = specs.train_batch_specs(cfg, ShapeConfig("t", "train", 256, 1))
    put = lambda t: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip), t)
    txt = jax.jit(step).lower(put(state), put(batch)).compile().as_text()

    pred = qcfg.policy.param_predicate()
    leaves = jax.tree_util.tree_flatten_with_path(
        abstract_params(registry(cfg.family).model_defs(cfg)))[0]
    n_quant = sum(1 for p, l in leaves if pred(p, l))
    assert n_quant < len(leaves)             # the norms stay out

    defs = {}
    for line in txt.splitlines():
        m = _INSTR.match(line)
        if m:
            defs[m.group(1)] = m.group(2)

    def opcode(rest):
        return _OPCODE.search(rest).group(1)

    def arg(rest):
        return re.search(r"\(%([\w.\-]+)\)", rest).group(1)

    def producer(name):
        """The instruction behind bitcasts and prefetches: async copies
        into another memory space that keep the shape and the layout."""
        while True:
            rest = defs[name]
            if opcode(rest) == "bitcast":
                name = arg(rest)
            elif opcode(rest) == "copy-done":
                src = arg(defs[arg(rest)])
                same = lambda r: re.sub(r"S\(\d+\)", "", r.split(" ")[0])
                if same(rest) != same(defs[src]):
                    return name, rest
                name = src
            else:
                return name, rest

    calls = [rest for rest in defs.values()
             if "tpu_custom_call" in rest and "custom-call(" in rest]
    assert len(calls) == 3 * n_quant
    for rest in calls:
        operands = [o.strip().lstrip("%")
                    for o in _OPERANDS.search(rest).group(1).split(",")]
        assert len(operands) == 2, rest[:200]   # [il, fl, seed] and x
        name, src = producer(operands[1])
        assert opcode(src) not in (
            "copy", "copy-start", "copy-done", "pad", "concatenate",
            "reshape", "transpose", "slice", "dynamic-slice"), \
            (name, src[:200])
        assert not re.search(r"copy|pad|concatenate|transpose", name), \
            (name, src[:200])


# the Mamba2 benchmark cell's scan, one layer: 4 x 2048 positions in
# chunks of 256, 64 heads of 64, state 128
SSD_B, SSD_S, SSD_Q, SSD_H, SSD_P, SSD_N = 4, 2048, 256, 64, 64, 128


def test_ssd_intra_kernels_compile(chip, monkeypatch):
    """The SSD scan's value and gradients at the Mamba2 cell's per-layer
    shapes in its dtypes, as ``ssd_scan`` runs them on a TPU: two Mosaic
    calls, the forward and the backward kernel, each carrying the scan's
    scope in its own op_name."""
    import dataclasses

    from repro.configs.base import get_config
    from repro.models import ssm

    monkeypatch.setattr(ssm, "on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("mamba2_1_3b"), ssm_chunk=SSD_Q)

    def value_and_grads(x, b, c, dt, ld, w):
        loss = lambda *a: jnp.sum(
            ssm.ssd_scan(cfg, *a)[0].astype(jnp.float32) * w)
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(
            x, b, c, dt, ld)

    B, S, H, P, N = SSD_B, SSD_S, SSD_H, SSD_P, SSD_N
    txt = _compile_text(value_and_grads, chip,
                        ((B, S, H, P), jnp.bfloat16),
                        ((B, S, N), jnp.bfloat16),
                        ((B, S, N), jnp.bfloat16),
                        ((B, S, H), jnp.float32),
                        ((B, S, H), jnp.float32),
                        ((B, S, H, P), jnp.float32))
    calls = [l for l in txt.splitlines() if "tpu_custom_call" in l
             and "custom-call(" in l]
    assert len(calls) == 2
    for name in ("ssd_intra_fwd", "ssd_intra_bwd"):
        assert sum(f"/ssm.scan/{name}/" in l for l in calls) == 1, name
