"""Compile rehearsal: the kernels on the training path, compiled for one
TPU v5e chip that is described, not attached.

Nothing runs; the TPU compiler (Mosaic for the Pallas kernels) accepts or
refuses the program, as it would on the chip. Interpret-mode parity cannot
catch what it refuses, such as block shapes off the (8, 128) tiling.
Shapes are the real ones: batch 8 at the train_4k sequence length and at
the 2048 tokens of the one-chip smoke run, slot rows lane-padded to 128
columns as ``pack_records`` ships them.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.chunk_gather.ops import chunk_gather, chunk_gather_train
from repro.kernels.common import round_up

pytestmark = pytest.mark.kernels

BATCH = 8


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described ``v5e:2x2``, with the persistent
    compilation cache off: a described chip's executables are written to
    it but cannot be read back without the chip."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"  # else libtpu logs under /tmp
    cache_on = jax.config.jax_enable_compilation_cache
    try:
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


def _tables(sharding):
    """(record_lens, indices) shapes: one slot per row."""
    spec = jax.ShapeDtypeStruct((BATCH,), jnp.int32, sharding=sharding)
    return spec, spec


def _assert_kernel_grids(compiled, width):
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel is in
    for out in jax.tree.leaves(compiled.out_info):
        assert out.shape == (BATCH, width)


@pytest.mark.parametrize("seq_len", [2048, 4096])
def test_chunk_gather_train_compiles_for_v5e(one_chip, seq_len):
    slots = jax.ShapeDtypeStruct(
        (BATCH, 1, round_up(seq_len + 1, 128)), jnp.int32, sharding=one_chip
    )
    compiled = chunk_gather_train.lower(
        slots, *_tables(one_chip), seq_len=seq_len, interpret=False
    ).compile()
    _assert_kernel_grids(compiled, seq_len)


def test_chunk_gather_compiles_for_v5e(one_chip):
    slots = jax.ShapeDtypeStruct((BATCH, 1, 2048), jnp.int32, sharding=one_chip)
    compiled = chunk_gather.lower(
        slots, *_tables(one_chip), interpret=False
    ).compile()
    _assert_kernel_grids(compiled, 2048)
