"""The router's Pallas kernels compile for a TPU v5e at the fleet's shapes.

Nothing runs: the TPU compiler, installed with jax, compiles for a v5e that
is described, not attached, and refuses what the chip's compiler would
refuse (tiling, VMEM use, device memory). The shapes are those the grid
engine's wide lowering gives the kernels on the chip: per tenant
GRID_POINTS λ rows or the 25-point octave ladder over the nine-arm pool,
vmapped over 4096 tenants.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import relax
from repro.kernels import awc_fw, topn_lp

TENANTS, ARMS = 4096, 9
CHIP_HBM = 16 * 2 ** 30          # TPU v5e: 16 GiB of HBM per chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < CHIP_HBM, used
    return compiled


@pytest.mark.parametrize("equality", [True, False])
def test_topn_lp_compiles_for_v5e(one_chip, no_compile_cache, equality):
    g = relax.GRID_POINTS
    fn = jax.vmap(functools.partial(topn_lp.topn_lp, equality=equality,
                                    interpret=False))
    _compile(fn, [((TENANTS, g, ARMS), jnp.float32),
                  ((TENANTS, g, ARMS), jnp.float32),
                  ((TENANTS, g), jnp.int32)], one_chip)


def test_awc_fw_compiles_for_v5e(one_chip, no_compile_cache):
    ladder = relax.LAM_MAX_EXP + 1
    fn = jax.vmap(functools.partial(awc_fw.awc_fw, interpret=False))
    row = ((TENANTS, 1, ARMS), jnp.float32)
    _compile(fn, [row, row, row, ((TENANTS, 1, ladder), jnp.float32),
                  ((TENANTS, 1), jnp.int32)], one_chip)
