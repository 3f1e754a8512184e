"""Executor satellites: the process-wide _JIT_CACHE must key on a
stable model fingerprint (id() reuse after GC must never hand a new
model another model's jitted closures, and sweeps must not grow the
cache without bound), and the pipelined restore must never leave the
module-global _ACTIVE_FEED published."""
import gc
import tempfile

import jax
import numpy as np

from conftest import tiny_model
from repro.configs import get_config, reduced
from repro.core import executor as executor_mod
from repro.core.executor import (_JIT_CACHE, _JIT_CACHE_MAX, _jit_cache_put,
                                 ModelExecutor, model_fingerprint)
from repro.core.service import LLMSConfig, LLMService
from repro.models.registry import build_model


def _build(d_model=64, n_heads=4):
    cfg = reduced(get_config("smollm-360m")).with_overrides(
        name=f"fp-test-{d_model}-{n_heads}", d_model=d_model,
        n_heads=n_heads, head_dim=d_model // n_heads)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return model, params


def test_no_cross_model_cache_hit_after_gc():
    """Build two differently-configured models back-to-back (the first
    garbage-collected, so the second may reuse its id()): the second
    must compile its own callables, never inherit the first's."""
    sc = LLMSConfig(policy="llms", max_ctx_len=64)
    model_a, params_a = _build(d_model=64)
    exe_a = ModelExecutor(model_a, params_a, sc)
    fp_a, decode_a = exe_a._fp, exe_a.decode_fn
    keys_a = {k for k in _JIT_CACHE if k[0] == fp_a}
    assert keys_a
    del model_a, params_a, exe_a
    gc.collect()

    model_b, params_b = _build(d_model=32)
    exe_b = ModelExecutor(model_b, params_b, sc)
    assert exe_b._fp != fp_a
    assert exe_b.decode_fn is not decode_a
    assert not keys_a & {k for k in _JIT_CACHE if k[0] == exe_b._fp}


def test_same_config_models_share_compilations():
    """The point of the process-wide cache: two models lowering to the
    same computation (same config + param tree) HIT, so policy/budget
    sweeps never recompile."""
    sc = LLMSConfig(policy="llms", max_ctx_len=64)
    model_a, params_a = _build(d_model=64)
    model_b, params_b = _build(d_model=64)
    assert model_fingerprint(model_a, params_a) == \
        model_fingerprint(model_b, params_b)
    exe_a = ModelExecutor(model_a, params_a, sc)
    exe_b = ModelExecutor(model_b, params_b, sc)
    assert exe_b.decode_fn is exe_a.decode_fn


def test_jit_cache_is_bounded():
    before = dict(_JIT_CACHE)
    try:
        for i in range(2 * _JIT_CACHE_MAX):
            _jit_cache_put(("bound-test", i), object())
        assert len(_JIT_CACHE) <= _JIT_CACHE_MAX
        # LRU: the most recent synthetic keys survived
        assert ("bound-test", 2 * _JIT_CACHE_MAX - 1) in _JIT_CACHE
        assert ("bound-test", 0) not in _JIT_CACHE
    finally:
        for k in [k for k in _JIT_CACHE if k[0] == "bound-test"]:
            del _JIT_CACHE[k]
        for k, v in before.items():     # restore anything LRU-evicted
            _JIT_CACHE.setdefault(k, v)


def test_active_feed_cleared_after_pipelined_restore():
    """Regression: run_pipelined used to leave the last restore's
    LayerFeed published forever (pinning its chunk buffers and exposing
    a stale feed to later retraces)."""
    cfg, model, params = tiny_model("smollm-360m")
    # paged_pool=False: the pipelined recompute restore is a slot-path
    # mechanism — paged switch-ins admit from payload/disk instead.
    sc = LLMSConfig(policy="llms", max_ctx_len=128, memory_budget=15_000,
                    swap_dir=tempfile.mkdtemp(), paged_pool=False)
    rng = np.random.RandomState(0)
    pipelined = {"n": 0}
    with LLMService(model, params, sc) as svc:
        orig = svc.exe.run_pipelined

        def spy(*a, **kw):
            assert executor_mod._ACTIVE_FEED is None    # unset on entry
            out = orig(*a, **kw)
            pipelined["n"] += 1
            return out
        svc.exe.run_pipelined = spy
        stubs = [svc.newLLMCtx() for _ in range(3)]
        for _ in range(3):      # tiny budget: every switch-in restores
            for stub in stubs:
                svc.callLLM(stub, rng.randint(1, cfg.vocab, 24).tolist(),
                            max_new_tokens=2)
        assert executor_mod._ACTIVE_FEED is None
        assert svc.stats()["pipelined_restores"] == pipelined["n"]
    assert pipelined["n"] > 0, "trace never exercised the pipelined path"
