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

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import dps_quant as dq
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
