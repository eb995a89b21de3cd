"""Compile the write path's Pallas kernels for a described TPU v5e.

The chip's compiler is installed with JAX and compiles for a topology that
is described, not attached, so these tests need no chip: they catch what
interpret mode cannot (block tiling rules, casts and layouts Mosaic
refuses) at the widths a real checkpoint uses. Nothing runs; each test
asserts that the compiled program holds the Pallas custom call.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and several test workers
import this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.adaptive_quant.kernel import quant_pack_pallas
from repro.kernels.adaptive_quant.ops import _resolve_steps
from repro.kernels.chunk_hash.kernel import chunk_hash_pallas

ROWS, DIM = 16384, 64        # one 16 Ki-row chunk of a dim-64 table
HASH_WORDS = 131072          # the word stream of a 64 Ki-row 4-bit chunk


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device compile cannot be read back without the chip:
    # keep any persistent cache out of these compiles
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", prior)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("method", ["adaptive", "uniform_asym"])
@pytest.mark.parametrize("bits", [2, 3, 4, 8])
def test_quant_pack_compiles_for_v5e(one_chip, bits, method):
    num_bins, n_steps = _resolve_steps(method, bits, None, None)
    x = jax.ShapeDtypeStruct((ROWS, DIM), jnp.float32, sharding=one_chip)
    compiled = quant_pack_pallas.lower(
        x, bits=bits, num_bins=num_bins, n_steps=n_steps).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_chunk_hash_compiles_for_v5e(one_chip):
    words = jax.ShapeDtypeStruct((HASH_WORDS,), jnp.uint32, sharding=one_chip)
    count = jax.ShapeDtypeStruct((), jnp.uint32, sharding=one_chip)
    compiled = chunk_hash_pallas.lower(words, count).compile()
    assert "tpu_custom_call" in compiled.as_text()
