"""Compile rehearsal: the kernels on the training path, compiled for one
TPU v5e chip that is described, not attached.

Nothing runs; the TPU compiler (Mosaic for the Pallas kernels) accepts or
refuses the program, as it would on the chip. Interpret-mode parity cannot
catch what it refuses, such as block shapes off the (8, 128) tiling.
Shapes are the real ones: batch 8 at the train_4k sequence length and at
the 2048 tokens of the one-chip smoke run, slot rows lane-padded to 128
columns as ``pack_records`` ships them; the attention kernels at the
benchmark cells' batch, sequence, heads and widths.
"""

import os
import re

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


def test_gather_kernel_op_keeps_its_name(one_chip):
    """The benchmark's ``chunk_gather_roofline`` finds the kernel in a
    device trace by the op's own instruction name inside the gather's
    module: the compiled module holds exactly one executed op that it
    matches, the kernel's custom call, named after ``pallas_call``'s
    ``name``. (The tuple elements read out of it execute nothing.)"""
    from bench.metrics import chunk_gather_roofline as reader

    seq_len = 512
    slots = jax.ShapeDtypeStruct(
        (16, 1, round_up(seq_len + 1, 128)), jnp.int32, sharding=one_chip
    )
    text = chunk_gather_train.lower(
        slots, *_tables(one_chip), seq_len=seq_len, interpret=False
    ).compile().as_text()
    assert text.split(",", 1)[0] == f"HloModule {reader.MODULE}"
    ops = [line.strip().removeprefix("ROOT ") for line in text.splitlines()[1:]
           if " = " in line and "get-tuple-element(" not in line]
    matched = [reader.instruction(op) for op in ops
               if reader.KERNEL.search(reader.instruction(op))]
    assert len(matched) == 1 and matched[0].startswith("chunk_gather_train.")
    assert "custom-call(" in next(op for op in ops if reader.instruction(op) == matched[0])


# ------------------------------------------------------- flash attention
#: (batch, seq, heads, kv heads): the deepseek-llm-7b and phi3-medium-4k
#: cells, and deepseek at 4,096 tokens (the chunked path's replacement).
ATTENTION_SHAPES = {
    "deepseek-s512": (16, 512, 32, 32),
    "phi3-s2048": (2, 2048, 40, 10),
    "deepseek-s4096": (1, 4096, 32, 32),
}
FLASH_KERNELS = ("flash_attention_fwd", "flash_attention_bwd")


def _kernel_ops(text):
    """{kernel name: [the op_name of each custom call that runs it]}. The
    instruction is named after the ``pallas_call``, with the transformations
    around it (``jvp_flash_attention_fwd_.1``)."""
    found = {name: [] for name in FLASH_KERNELS}
    for line in text.splitlines():
        if " = " not in line or "custom-call(" not in line:
            continue
        instruction = line.split(" = ", 1)[0]
        for name in FLASH_KERNELS:
            if name in instruction:
                found[name].append(re.search(r'op_name="([^"]*)"', line).group(1))
    return found


@pytest.mark.parametrize("shape", ATTENTION_SHAPES.values(), ids=ATTENTION_SHAPES.keys())
def test_flash_attention_train_compiles_for_v5e(one_chip, shape):
    """The forward and the backward kernel compile at the cells' shapes."""
    from repro.kernels.flash_attention.ops import flash_attention_train

    b, s, h, kvh = shape
    spec = lambda n: jax.ShapeDtypeStruct((b, s, n, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention_train(q, k, v, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        spec(h), spec(kvh), spec(kvh)).compile()
    assert all(len(ops) == 1 for ops in _kernel_ops(compiled.as_text()).values())


def test_latent_attention_compiles_for_v5e(one_chip):
    """DeepSeek-V2-Lite's cell: B=4, S=4,096, 16 heads, q·k over 256 lanes
    (192 zero-padded) and values 128 wide, with an explicit scale."""
    from repro.kernels.flash_attention.ops import flash_attention_train

    b, s, h = 4, 4096, 16
    spec = lambda d: jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention_train(q, k, v, scale=0.1147214, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, (0, 1, 2))).lower(
        spec(256), spec(256), spec(128)).compile()
    assert all(len(ops) == 1 for ops in _kernel_ops(compiled.as_text()).values())


def test_train_attention_takes_the_kernel_on_v5e(one_chip, monkeypatch):
    """phi3's attention layer, forward and backward, lowered for the chip:
    the kernel's custom calls sit under the ``attention`` scope that
    ``attention_ms`` reads, and its temporaries are smaller than the jnp
    path's (which the test forces)."""
    from bench.metrics.train_scope import scope_of
    from repro.configs import ModelConfig
    from repro.models import attention

    b, s, h, kvh = ATTENTION_SHAPES["phi3-s2048"]
    d = h * 128
    cfg = ModelConfig(name="phi3", family="dense", num_layers=1, d_model=d,
                      num_heads=h, num_kv_heads=kvh, d_ff=128, vocab_size=128,
                      head_dim=128)
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    params = {"wq": spec(d, d), "wk": spec(d, kvh * 128), "wv": spec(d, kvh * 128),
              "wo": spec(d, d)}

    def layer_grads(p, x):
        def loss(p, x):
            with jax.named_scope("attention"):
                out, _ = attention.attention_block(p, x, cfg)
            return jnp.sum(out.astype(jnp.float32))

        return jax.grad(loss, (0, 1))(p, x)

    def compile_layer():  # a new function each time: jit keeps traces by function
        return jax.jit(lambda p, x: layer_grads(p, x)).lower(params, spec(b, s, d)).compile()

    kernel = compile_layer()
    ops = _kernel_ops(kernel.as_text())
    assert [len(ops[n]) for n in FLASH_KERNELS] == [1, 1]
    assert all(scope_of(op) == "attention" for v in ops.values() for op in v)
    monkeypatch.setattr(attention, "_kernel_applies", lambda *a: False)
    dense = compile_layer()
    assert not any(_kernel_ops(dense.as_text()).values())
    kernel_tmp = kernel.memory_analysis().temp_size_in_bytes
    dense_tmp = dense.memory_analysis().temp_size_in_bytes
    assert kernel_tmp < dense_tmp / 4, (kernel_tmp, dense_tmp)
