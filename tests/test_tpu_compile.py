"""The main-path Pallas kernels compile for a described TPU v5e at
smollm-360m's real widths, with no chip attached.  Each test asserts
that the compiled program holds the kernel (``tpu_custom_call``), so a
kernel the chip's compiler refuses fails here at no chip time.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library, and every test
worker imports every test file."""
import math
import os
import re

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


# cell -> (configuration file, traffic file, the configuration's module
# under chipbench/configs)
CELLS = {"smollm-360m.switch": ("smollm-360m", "switch", "dense"),
         "qwen2.5-14b.decode": ("qwen2.5-14b", "decode", "dense"),
         "deepseek-v2-lite.decode": ("deepseek-v2-lite", "decode_moe",
                                     "mla_moe")}


def _cell(cell, sharding):
    """The model of a benchmark cell at its published widths, shape
    specs of its parameters and page arenas, and its page-table width:
    -> (model, params, arenas, service settings, C, spec maker)."""
    import importlib
    import json
    from pathlib import Path

    from repro.configs.base import ModelConfig
    from repro.models.registry import build_model

    cfg_name, traffic, module = CELLS[cell]
    root = Path(__file__).resolve().parent.parent / "chipbench"
    cfg = json.loads((root / "configs" / f"{cfg_name}.json").read_text())
    sv = json.loads((root / "traffic" / f"{traffic}.json").read_text()
                    )["service"]
    fam = importlib.import_module(f"chipbench.configs.{module}")
    model = build_model(ModelConfig(**fam.program_config(cfg)))

    def s(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    params = jax.tree.map(lambda a: s(a.shape, a.dtype),
                          jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    spec = model.kv_spec()
    arenas = {n + "16": s((model.cfg.n_layers, sv["pool_pages_16"], T,
                           *spec.leaf_dims[n]), jnp.bfloat16)
              for n in spec.seq_leaves}
    C = 2 * sv["max_ctx"] // T      # window plus the largest prompt bucket
    return model, params, arenas, sv, C, s


def _arena_copies(text, arenas):
    """``copy`` ops in a compiled program whose result has an arena's
    shape: each one rewrites a whole arena."""
    shapes = "|".join(",".join(map(str, a.shape)) for a in arenas.values())
    return re.findall(rf"= bf16\[(?:{shapes})\]\{{[^}}]*\}} copy\(", text)


def test_paged_decode_program_reads_pages_in_place(one_chip, monkeypatch):
    """The executor's paged decode program at ``qwen2.5-14b.decode``'s
    shapes takes the in-place kernel: no buffer of the dense ``(L, B, S,
    KV, hd)`` view anywhere in the program, and its temporaries stay
    small (2.35 GB with the view).  It donates its arenas and writes the
    round's rows into them: the arenas alias its output, and no copy of
    an arena is left in it (two without donation)."""
    from repro.core.executor import decode_donates, paged_decode_program
    from repro.models import common

    monkeypatch.setattr(common, "on_tpu", lambda: True)
    model, params, arenas, sv, C, s = _cell("qwen2.5-14b.decode", one_chip)
    mc, B = model.cfg, sv["decode_batch"]
    assert decode_donates(model)
    compiled = paged_decode_program(model).lower(
        params, s((B, 1), jnp.int32), arenas,
        s((B, C), jnp.int32), None, None, s((B,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes <= 0.25e9
    view = mc.n_layers * B * C * T * mc.n_kv_heads * mc.head_dim
    sizes = {math.prod(int(d) for d in dims.split(","))
             for dims in re.findall(r"\b[a-z]+\d*\[([\d,]+)\]", text)}
    assert view not in sizes
    assert ma.alias_size_in_bytes >= sum(
        math.prod(a.shape) * a.dtype.itemsize for a in arenas.values())
    assert not _arena_copies(text, arenas)


@pytest.mark.parametrize("cell", CELLS)
def test_arena_writers_donate_the_arenas(one_chip, monkeypatch, cell):
    """At each cell's shapes the executor's page writes (``admit16``,
    ``zero16``) and paged prefill-append donate the page arenas: the
    arenas alias each program's output, and a page write copies no
    arena (two whole-arena copies each without donation).  The decode
    round donates where it reads its pages in place (the dense cell);
    the gather path of the others keeps its arguments."""
    from repro.core.executor import (decode_donates, page_programs,
                                     paged_extend_program)
    from repro.models import common

    monkeypatch.setattr(common, "on_tpu", lambda: True)
    model, params, arenas, sv, C, s = _cell(cell, one_chip)
    spec, L = model.kv_spec(), model.cfg.n_layers
    assert decode_donates(model) is (cell == "qwen2.5-14b.decode")
    arena_bytes = sum(math.prod(a.shape) * a.dtype.itemsize
                      for a in arenas.values())
    progs = page_programs(spec.seq_leaves, spec.leaf_dims, T, L)
    page = s((), jnp.int32)
    blocks = {n: s((T, L * math.prod(spec.leaf_dims[n])), jnp.bfloat16)
              for n in spec.seq_leaves}
    M = 256                                         # one prompt bucket
    compiled = {
        "admit16": progs["admit16"].lower(arenas, page, blocks),
        "zero16": progs["zero16"].lower(arenas, page),
        "extend": paged_extend_program(model).lower(
            params, s((1, M), jnp.int32), s((M,), jnp.int32), arenas,
            s((1, C), jnp.int32), None, None, s((), jnp.int32)),
    }
    for name, lowered in compiled.items():
        c = lowered.compile()
        assert c.memory_analysis().alias_size_in_bytes >= arena_bytes, name
        if name != "extend":
            assert not _arena_copies(c.as_text(), arenas), name


def test_mla_decode_program_fits(one_chip):
    """The executor's paged decode program at ``deepseek-v2-lite.decode``'s
    shapes: every routed layer's three projections run as one ragged dot
    over the group's whole flattened expert stack (sliced per layer, the
    stacks were copied: 5.95 GB of temporaries, over the chip), and
    arguments, outputs and temporaries fit the chip's 16 GB with room
    for the runtime (12.95 GB)."""
    from repro.core.executor import paged_decode_program

    model, params, arenas, sv, C, s = _cell("deepseek-v2-lite.decode",
                                            one_chip)
    mc, B = model.cfg, sv["decode_batch"]
    compiled = paged_decode_program(model).lower(
        params, s((B, 1), jnp.int32), arenas, s((B, C), jnp.int32), None,
        None, s((B,), jnp.int32)).compile()
    text = compiled.as_text()
    n_moe = mc.n_layers - mc.moe.first_dense
    stack = rf"bf16\[{n_moe * mc.moe.n_experts},"
    dots = re.findall(r"%ragged-dot-none[.\d]* = .*", text)
    assert len(dots) == 3 * n_moe
    assert all(re.search(stack, d) for d in dots)
    ma = compiled.memory_analysis()
    total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
             + ma.temp_size_in_bytes)
    assert total <= 13.5e9, total
