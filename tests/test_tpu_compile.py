"""The main-path Pallas kernels compile for a described TPU v5e at
smollm-360m's real widths, with no chip attached.  Each test asserts
that the compiled program holds the kernel (``tpu_custom_call``), so a
kernel the chip's compiler refuses fails here at no chip time.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and every test
worker imports every test file."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import chunk_quant, decode_qattn as kdq

CFG = get_config("smollm-360m")
T = 16                                              # chunk tokens
F = CFG.n_layers * CFG.n_kv_heads * CFG.head_dim    # one chunk row: 10240
H, KV, HD = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"      # else the compiler logs to /tmp
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        cc.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


def assert_kernel_compiles(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bits", [8, 4, 2])
def test_chunk_quantize_compiles(one_chip, bits):
    x = jax.ShapeDtypeStruct((T, F), jnp.bfloat16, sharding=one_chip)
    assert_kernel_compiles(lambda x: chunk_quant.quantize(x, bits), x)


@pytest.mark.parametrize("bits", [8, 4, 2])
def test_chunk_dequantize_compiles(one_chip, bits):
    packed = jax.ShapeDtypeStruct((T * bits // 8, F), jnp.int8,
                                  sharding=one_chip)
    scale = jax.ShapeDtypeStruct((F,), jnp.float32, sharding=one_chip)
    assert_kernel_compiles(
        lambda p, s: chunk_quant.dequantize(p, s, bits, T), packed, scale)


def _decode_shapes(sharding, B, S, mixed):
    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    cache = [s((B, S, KV, HD), jnp.int8)] * 2 + [s((B, S, KV), jnp.float32)] * 2
    if mixed:
        cache = ([s((B, S, KV, HD), jnp.bfloat16)] * 2 + cache
                 + [s((B, S), jnp.bool_)])
    return [s((B, H, HD), jnp.bfloat16)] + cache + [s((B,), jnp.int32)]


@pytest.mark.parametrize("B,S", [(1, 512), (4, 2048)])
def test_decode_mqattn_compiles(one_chip, B, S):
    assert_kernel_compiles(kdq.decode_mqattn,
                           *_decode_shapes(one_chip, B, S, mixed=True))


def test_decode_qattn_compiles(one_chip):
    assert_kernel_compiles(kdq.decode_qattn,
                           *_decode_shapes(one_chip, 4, 2048, mixed=False))
