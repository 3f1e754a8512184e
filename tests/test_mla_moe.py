"""DeepSeek-V2-Lite's pieces on the CPU at a small size (one dense and
two routed layers, 8 experts top-2 and a shared expert, latent rank 32,
rope 8, YaRN factor 4): the program served through the paged pool
against the plain float32 reference of ``chipbench/configs/mla_moe.py``
on logits; the dropless, batch-independent expert layer; YaRN's
frequencies and softmax scale at the published config; and the paged
decode round over latent arenas."""
import dataclasses
import functools
import math
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness
from repro.configs import get_config
from repro.configs.base import ModelConfig, MoEConfig
from repro.models.mla import softmax_scale, yarn_inv_freq
from repro.models.moe_layer import moe_ffn
from repro.models.registry import build_model

# hidden 256: at 64 a single routing flip between near-tied experts (the
# bf16 program and the f32 reference rounding the router's input apart)
# moves the logits by up to 0.4; at 256, as at the published width (0.06
# at most on the chip), by a few hundredths
SMALL = dict(hidden_size=256, intermediate_size=256, moe_intermediate_size=64,
             num_hidden_layers=3, first_k_dense_replace=1,
             num_attention_heads=4, num_key_value_heads=4, kv_lora_rank=32,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
             vocab_size=8192,
             rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 4,
                           "mscale": 0.707, "mscale_all_dim": 0.707,
                           "original_max_position_embeddings": 64,
                           "type": "yarn"})


@functools.lru_cache(maxsize=None)
def small_cell(seed):
    bench = harness.load_benchmark()
    cfg = dict(harness.load_config(bench, "deepseek-v2-lite"), **SMALL)
    fam = harness.family(cfg)
    model = build_model(ModelConfig(**fam.program_config(cfg)))
    return cfg, fam, model, fam.make_params(cfg, seed)


# --------------------------------------------------------------------- #
# YaRN at the published configuration
# --------------------------------------------------------------------- #
def test_yarn_at_published_config():
    """DeepSeek-V2-Lite: rope 64 wide, theta 1e4, factor 40 over 4096
    original positions, beta_fast 32 / beta_slow 1.  The correction
    range is [floor(10.47), ceil(22.51)] = [10, 23]: frequencies 0-10
    stay, 23-31 are divided by 40, the ones between blend linearly;
    mscale(40, 0.707) = 1 + 0.0707 ln 40 = 1.26080."""
    m = get_config("deepseek-v2-lite-16b").mla
    inv = yarn_inv_freq(m, 10000.0)
    plain = 10000.0 ** (-np.arange(32) / 32)
    assert inv.shape == (32,)
    np.testing.assert_allclose(inv[:11], plain[:11], rtol=1e-6)
    np.testing.assert_allclose(inv[23:], plain[23:] / 40, rtol=1e-6)
    # i = 16: 6/13 of the way, 0.01 * (6/13 / 40 + 7/13)
    np.testing.assert_allclose(inv[16], 0.0055, rtol=1e-6)
    np.testing.assert_allclose(inv[22], 10 ** -2.75 * 0.1, rtol=1e-6)
    mscale = 1 + 0.1 * 0.707 * math.log(40)
    assert mscale == pytest.approx(1.2608038, abs=1e-7)
    assert softmax_scale(m) == pytest.approx(192 ** -0.5 * mscale ** 2,
                                             rel=1e-12)
    assert softmax_scale(m) == pytest.approx(0.1147214, abs=1e-7)
    # factor 1 is plain rope at 1/sqrt(qk_head_dim)
    plain_m = dataclasses.replace(m, factor=1.0, mscale_all_dim=0.0)
    np.testing.assert_allclose(yarn_inv_freq(plain_m, 10000.0), plain,
                               rtol=1e-6)
    assert softmax_scale(plain_m) == pytest.approx(192 ** -0.5)


def test_reference_yarn_matches_the_program():
    """The reference computes the same frequencies and scale on its own
    (it imports nothing of the program)."""
    cfg, fam, model, _ = small_cell(0)
    for c in (cfg, harness.load_config(harness.load_benchmark(),
                                       "deepseek-v2-lite")):
        s = fam.sizes(c)
        m = ModelConfig(**fam.program_config(c)).mla
        inv, cs, scale = fam.yarn(s)
        np.testing.assert_allclose(inv, yarn_inv_freq(m, s["theta"]),
                                   rtol=1e-6)
        assert cs == 1.0
        assert scale == pytest.approx(softmax_scale(m), rel=1e-12)


# --------------------------------------------------------------------- #
# the expert layer
# --------------------------------------------------------------------- #
MOE = MoEConfig(n_experts=8, top_k=2, d_expert=32, n_shared=1, d_shared=32)


def _moe_params(key, d=64, dtype=jnp.float32, bias=None):
    ks = jax.random.split(key, 7)

    def w(k, shape, fan):
        return (jax.random.normal(k, shape) / math.sqrt(fan)).astype(dtype)
    p = {"router": w(ks[0], (d, 8), d),
         "w_gate": w(ks[1], (8, d, 32), d), "w_up": w(ks[2], (8, d, 32), d),
         "w_down": w(ks[3], (8, 32, d), 32),
         "s_gate": w(ks[4], (d, 32), d), "s_up": w(ks[5], (d, 32), d),
         "s_down": w(ks[6], (32, d), 32)}
    if bias is not None:           # every token prefers these experts
        p["router"] = p["router"] + jnp.zeros((d, 8)).at[:, bias].set(
            10.0 / math.sqrt(d))
    return p


def _expected(x, p, moe):
    """Each token through its own top-k experts, one at a time."""
    scores = jax.nn.softmax(x @ p["router"], -1)
    w, idx = jax.lax.top_k(scores, moe.top_k)
    out = []
    for t in range(x.shape[0]):
        y = (jax.nn.silu(x[t] @ p["s_gate"]) * (x[t] @ p["s_up"])) \
            @ p["s_down"]
        for j in range(moe.top_k):
            e = int(idx[t, j])
            h = jax.nn.silu(x[t] @ p["w_gate"][e]) * (x[t] @ p["w_up"][e])
            y = y + w[t, j] * (h @ p["w_down"][e])
        out.append(y)
    return jnp.stack(out), idx


def test_expert_layer_is_dropless():
    """64 tokens all routed to experts 3 and 5: a capacity of
    ``ceil(64 * 2 * 1.25 / 8)`` = 20 rows an expert would drop most of
    them; here each gets both experts' full output."""
    key = jax.random.PRNGKey(0)
    p = _moe_params(key, bias=jnp.array([3, 5]))
    # inputs of positive mean, so the biased columns win for every token
    x = jax.random.normal(jax.random.fold_in(key, 1), (64, 64)) + 3.0
    y, ids = moe_ffn(x, p, MOE)
    assert set(np.unique(np.asarray(ids))) == {3, 5}
    want, want_ids = _expected(x, p, MOE)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    # float32 throughout: only the summation order differs
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-5,
                               atol=1e-5)


def test_expert_layer_is_batch_independent():
    """A row's output is the same, bit for bit, alone as among 7 other
    rows (bf16, as served), and the stacked-layer form gives the same
    as the layer's own experts."""
    key = jax.random.PRNGKey(1)
    p = _moe_params(key, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.fold_in(key, 1), (8, 64)
                          ).astype(jnp.bfloat16)
    y8, _ = moe_ffn(x, p, MOE)
    for r in range(8):
        y1, _ = moe_ffn(x[r:r + 1], p, MOE)
        np.testing.assert_array_equal(np.asarray(y1[0], np.float32),
                                      np.asarray(y8[r], np.float32))
    # layer 1 of a 3-layer stack, flattened to (3 * E, ...)
    other = _moe_params(jax.random.fold_in(key, 2), dtype=jnp.bfloat16)
    stacked = dict(p)
    for k in ("w_gate", "w_up", "w_down"):
        stacked[k] = jnp.concatenate([other[k], p[k], other[k]])
    ys, _ = moe_ffn(x, stacked, MOE, layer=1)
    np.testing.assert_array_equal(np.asarray(ys, np.float32),
                                  np.asarray(y8, np.float32))


# --------------------------------------------------------------------- #
# the served program against the plain reference
# --------------------------------------------------------------------- #
def test_mla_serves_on_the_paged_pool():
    cfg, fam, model, params = small_cell(0)
    spec = model.kv_spec()
    assert spec.paged and spec.batched_decode
    assert model.cfg.moe.first_dense == 1
    assert set(params) == {"embed", "head", "ln_f", "dense", "layers"}
    assert params["dense"]["w_gate"].shape == (1, 256, 256)
    assert params["layers"]["w_gate"].shape == (2, 8, 256, 64)


def test_served_program_matches_reference():
    """Two contexts served through ``ServiceRouter`` over ``LLMService``
    on the paged pool (decode batch 2): each prompt's prefill, then
    12 paged decode rounds, against the reference's one forward pass
    over prompt and answer, on the logits of every served position.
    Tolerances: 0.05 on every logit, the bf16 program's rounding of its
    weights' products, activations and latents through three layers
    against float32 (0.02-0.03 measured); the served token within
    0.02 of the reference's best (a near-tie may be served either
    way)."""
    from chipbench.harness import probe_sampling
    from repro.core.scheduler import ServiceRouter
    from repro.core.service import LLMSConfig, LLMService

    cfg, fam, model, params = small_cell(0)
    V = cfg["vocab_size"]
    svc = LLMService(model, params, LLMSConfig(
        policy="llms_nocomp", max_ctx_len=256, memory_budget=10**9,
        decode_batch=2, paged_pool=True, chunk_tokens=16,
        swap_dir=tempfile.mkdtemp()))
    assert svc.paged
    router = ServiceRouter(svc, predict=True, start=True, slice_steps=4)
    rng = np.random.default_rng(0)
    app = router.register_app("app")
    stubs = [app.new_ctx() for _ in range(2)]
    prompts = [rng.integers(1, V, n).astype(np.int32) for n in (40, 23)]
    sinks = [[], []]
    streams = [app.stream(st, p.tolist(), max_new_tokens=12,
                          sampling=probe_sampling(np.arange(V), sink))
               for st, p, sink in zip(stubs, prompts, sinks)]
    tokens = [s.result(300) for s in streams]
    router.abort()
    svc.close()
    ref = fam.Reference(cfg, 256, 16, 16)
    for p, toks, sink in zip(prompts, tokens, sinks):
        assert len(toks) == 12
        seq = np.concatenate([p, toks])
        _, lg = ref.extend(params, ref.new_store(), seq[:-1], 0,
                           len(seq) - 1, list(range(len(p) - 1,
                                                    len(seq) - 1)))
        prog = np.asarray(sink, np.float32)[:, :-1]
        np.testing.assert_allclose(prog, lg, rtol=0, atol=0.05)
        gap = lg.max(axis=1) - lg[np.arange(12), toks]
        assert gap.max() <= 0.02


def test_program_routes_as_the_reference():
    """The experts the program's decode step picks, token by token, are
    the reference's top-2 at almost every (token, layer): a pair may
    differ only where two experts' scores are near-tied and bf16 and
    float32 order them apart (5% of pairs at the published width on
    the chip)."""
    cfg, fam, model, params = small_cell(0)
    rng = np.random.default_rng(1)
    toks = rng.integers(1, cfg["vocab_size"], 48).astype(np.int32)
    n0 = 16
    cache = model.init_cache(1, 64)
    pf = model.prefill(params, {"tokens": jnp.asarray(toks[:n0])[None]})
    for n in ("ckv", "kpe"):
        cache[n] = cache[n].at[:, :, :n0].set(pf.cache[n])
    cache["pos"] = jnp.int32(n0)
    dec = jax.jit(model.decode_step)
    picked = []
    for t in toks[n0:]:
        out = dec(params, jnp.asarray([[t]], jnp.int32), cache)
        cache = out.cache
        picked.append(np.asarray(out.experts[:, 0]))          # (L_moe, k)
    ref = fam.Reference(cfg, 256, 16, 32)
    ref.extend(params, ref.new_store(), toks, 0, len(toks),
               list(range(n0, len(toks))))
    want = ref.routes                                      # (L_moe, 32, k)
    assert want.shape == (2, 32, 2)
    same = [set(picked[i][l]) == set(want[l, i])
            for i in range(32) for l in range(2)]
    assert np.mean(same) >= 0.9, np.mean(same)


_LOWER = """
import hashlib, jax, jax.numpy as jnp
from repro.configs import get_config, reduced
from repro.core.executor import paged_decode_program
from repro.models.registry import build_model
model = build_model(reduced(get_config("deepseek-v2-lite-16b")))
m, L = model.cfg.mla, model.cfg.n_layers
s = jax.ShapeDtypeStruct
params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
arenas = {"ckv16": s((L, 9, 16, m.kv_lora_rank), jnp.bfloat16),
          "kpe16": s((L, 9, 16, m.qk_rope_head_dim), jnp.bfloat16)}
i32 = jnp.int32
text = paged_decode_program(model).lower(
    params, s((2, 1), i32), arenas, s((2, 4), i32), None, None,
    s((2,), i32)).as_text()
print(hashlib.sha256(text.encode()).hexdigest())
"""


def test_program_text_is_the_same_in_every_process():
    """The paged decode program lowers to the same text in processes with
    different string hash seeds, so each process of a cell finds the
    programs that an earlier one left in the persistent compile cache
    (joining the layer groups' outputs in a set's order once gave each
    process one of several program texts)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = str(Path(__file__).resolve().parent.parent / "src")
    texts = set()
    for seed in ("1", "2", "3"):
        env = dict(os.environ, PYTHONHASHSEED=seed, JAX_PLATFORMS="cpu",
                   PYTHONPATH=src + os.pathsep + os.environ.get(
                       "PYTHONPATH", ""))
        out = subprocess.run([sys.executable, "-c", _LOWER], env=env,
                             capture_output=True, text=True, check=True)
        texts.add(out.stdout.strip().splitlines()[-1])
    assert len(texts) == 1, texts
