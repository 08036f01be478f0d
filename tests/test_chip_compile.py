"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: the TPU compiler installed with JAX compiles each kernel
at qwen3-0.6b widths (16 query / 8 kv heads, head_dim 128, max_len 512,
the prompt buckets the engine serves) for one chip of a ``v5e:2x2``
topology that is described, not attached. This is what interpret-mode
tests cannot show: the TPU lowering refuses block shapes that the
interpreter accepts. The topology is described inside a fixture, never
at import, and every test that needs it lives in this one file: one
process at a time may load the TPU library, and under pytest-xdist only
the worker given this file should.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

H, HKV, HD, MAX_LEN = 16, 8, 128, 512          # qwen3-0.6b attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler otherwise writes its logs under /tmp
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache out
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield jax.sharding.SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)


def _compiles_to_kernel(sharding, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("seq", [16, 128])
def test_flash_attention_compiles(one_chip, seq):
    from repro.kernels.flash_attention import flash_attention
    f32 = jnp.float32
    _compiles_to_kernel(one_chip, flash_attention, ((2, seq, H, HD), f32),
                        ((2, seq, HKV, HD), f32), ((2, seq, HKV, HD), f32))


@pytest.mark.parametrize("batch,dtype", [(2, jnp.float32),
                                         (2, jnp.bfloat16),
                                         (8, jnp.float32)])
def test_decode_attention_compiles(one_chip, batch, dtype):
    """The engine's dense default (2 slots, max_len 512): refused by the
    TPU lowering while the mask rode as a (1, block_k) block."""
    from repro.kernels.decode_attention import decode_attention
    _compiles_to_kernel(one_chip, decode_attention, ((batch, H, HD), dtype),
                        ((batch, MAX_LEN, HKV, HD), dtype),
                        ((batch, MAX_LEN, HKV, HD), dtype),
                        ((batch, MAX_LEN), jnp.bool_))


def test_decode_attention_int8_compiles(one_chip):
    from repro.kernels.decode_attention import decode_attention_int8
    f32, i8 = jnp.float32, jnp.int8
    _compiles_to_kernel(one_chip, decode_attention_int8, ((2, H, HD), f32),
                        ((2, MAX_LEN, HKV, HD), i8),
                        ((2, MAX_LEN, HKV, HD), i8),
                        ((2, MAX_LEN), jnp.bool_),
                        ((2, MAX_LEN, HKV), f32), ((2, MAX_LEN, HKV), f32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_compiles(one_chip, dtype):
    """The paged default: 2 slots × 512 / 16 = 64 pages (+ scratch) and
    64 resident rows, block_size 16."""
    from repro.kernels.paged_attention import paged_decode_attention
    i32 = jnp.int32
    _compiles_to_kernel(one_chip, paged_decode_attention,
                        ((64, H, HD), dtype), ((65, 16, HKV, HD), dtype),
                        ((65, 16, HKV, HD), dtype), ((64, 32), i32),
                        ((64,), i32))


def test_rmsnorm_compiles(one_chip):
    from repro.kernels.rmsnorm import rmsnorm
    f32 = jnp.float32
    _compiles_to_kernel(one_chip, rmsnorm, ((2, 128, 1024), f32),
                        ((1024,), f32))


def test_mla_decode_compiles(one_chip):
    """deepseek-v2-lite's latent decode: kv_lora_rank 512, rope dim 64."""
    from repro.kernels.mla_decode import mla_decode_ctx
    f32 = jnp.float32
    _compiles_to_kernel(
        one_chip, lambda a, b, c, d, e: mla_decode_ctx(a, b, c, d, e,
                                                       scale=0.07),
        ((2, 16, 512), f32), ((2, 16, 64), f32), ((2, MAX_LEN, 512), f32),
        ((2, MAX_LEN, 64), f32), ((2, MAX_LEN), jnp.bool_))


def test_ssd_scan_compiles(one_chip):
    """mamba2-2.7b's SSD scan: 80 heads of 64, state 128, chunk 256."""
    from repro.kernels.ssd_scan import ssd_scan
    f32 = jnp.float32
    _compiles_to_kernel(
        one_chip, lambda *a: ssd_scan(*a, chunk=256),
        ((1, 512, 80, 64), f32), ((1, 512, 80), f32), ((80,), f32),
        ((1, 512, 1, 128), f32), ((1, 512, 1, 128), f32), ((80,), f32))
