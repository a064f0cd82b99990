"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing runs: each test lowers and compiles a kernel for a described
(not attached) v5e chip, which is where Mosaic refuses a block that is
not aligned to the tiling or a gather it cannot lower, things interpret
mode accepts. The topology is described inside a fixture, so that only
the worker given this file loads the TPU compiler.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.decode_attention import \
    decode_attention_kernel
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_kernel
from repro.kernels.fused_rl_loss.fused_rl_loss import (
    fused_rl_loss_bwd_kernel, fused_rl_loss_fwd_kernel)

N_ROWS = 4096                     # tokens per actor micro-batch
# Qwen2.5-7B attention: 28 query / 4 KV heads of 128
HEADS, KV_HEADS, HEAD_DIM, SEQ = 28, 4, 128, 2048


@pytest.fixture(scope="module")
def no_compile_cache():
    """A program compiled for a described chip is written to the
    persistent cache but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                           # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _kernel_compiled(fn, chip, *shapes):
    """Compile ``fn`` for ``chip`` from (shape, dtype) pairs; True when
    the compiled program holds a Mosaic kernel."""
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return "tpu_custom_call" in text


@pytest.mark.parametrize("vocab", [19_008, 152_064])
def test_fused_rl_loss_forward_compiles(one_chip, vocab):
    row = ((N_ROWS,), jnp.float32)
    assert _kernel_compiled(fused_rl_loss_fwd_kernel, one_chip,
                            ((N_ROWS, vocab), jnp.bfloat16),
                            ((N_ROWS,), jnp.int32), row, row, row)


@pytest.mark.parametrize("vocab", [19_008, 152_064])
def test_fused_rl_loss_backward_compiles(one_chip, vocab):
    row = ((N_ROWS,), jnp.float32)
    assert _kernel_compiled(fused_rl_loss_bwd_kernel, one_chip,
                            ((N_ROWS, vocab), jnp.bfloat16),
                            ((N_ROWS,), jnp.int32), row, row, row, row)


def test_flash_attention_compiles(one_chip):
    kv = ((1, SEQ, KV_HEADS, HEAD_DIM), jnp.bfloat16)
    assert _kernel_compiled(flash_attention_kernel, one_chip,
                            ((1, SEQ, HEADS, HEAD_DIM), jnp.bfloat16), kv, kv)


def test_decode_attention_compiles(one_chip):
    B = 8
    kv = ((B, SEQ, KV_HEADS, HEAD_DIM), jnp.bfloat16)
    assert _kernel_compiled(decode_attention_kernel, one_chip,
                            ((B, 1, HEADS, HEAD_DIM), jnp.bfloat16), kv, kv,
                            ((B, SEQ), jnp.bool_))
