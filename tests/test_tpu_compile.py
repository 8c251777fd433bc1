"""Compile the Pallas kernels for a described TPU v5e, without the chip.

The TPU compiler is installed with jax; it compiles for a chip that is
described and not attached.  Nothing runs, so these tests say nothing about
results (tests/test_kernels.py checks those in interpret mode): they catch
what only the real lowering refuses, at the widths the system serves.

The topology is described inside a fixture, never at import: only one process
at a time may load the TPU library, and every test worker imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.crc32 import crc32_pallas
from repro.kernels.flash_attention import flash_attention_pallas


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep the cache out of these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not interpreted
    return compiled


@pytest.mark.parametrize("n,w", [(4096, 256), (1024, 1024)])
def test_crc32_kernel_compiles_for_v5e(one_chip, no_compile_cache, n, w):
    data = jax.ShapeDtypeStruct((n, w), jnp.uint32, sharding=one_chip)
    _compile(crc32_pallas, data)


def test_flash_attention_compiles_for_v5e_at_olmo_1b_widths(
        one_chip, no_compile_cache):
    # olmo_1b: 16 heads × head_dim 128; batch 4 → 64 (batch·head) rows
    qkv = jax.ShapeDtypeStruct((64, 2048, 128), jnp.bfloat16, sharding=one_chip)
    _compile(flash_attention_pallas, qkv, qkv, qkv)
