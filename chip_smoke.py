"""Serve smollm-360m at full published width on one TPU chip, once, and
check what comes out.

    python chip_smoke.py

Runs in one process and needs a TPU: with any other device it exits 2
before doing anything.  Weights are random, made from ``SEED``; nothing
is downloaded.  Phases:

1. kernels: the Pallas chunk codec (8/4/2 bits, one chunk row) and the
   fused mixed-precision decode-attention kernel against their jnp
   oracles (``kernels/ref.py``), on the chip;
2. paged serving: ``LLMService`` + ``ServiceRouter`` under policy
   ``llms`` with the paged pool and ``decode_batch`` 4, driven by
   ``launch/serve.run_trace``.  The byte budget is an eighth of the
   contexts' KV, so chunks are compressed (the planner picks 8, 4 or 2
   bits per chunk), swapped to disk and read back;
3. reference: each context's first call against a plain
   ``model.prefill`` + ``decode_step`` greedy loop;
4. slot-engine restore: the layer-pipelined IO + recompute restore
   (paper Fig. 8) is a slot-engine path the paged engine never takes, so
   a short run with the paged pool off exercises it.

Earlier lines report the device, JAX version, timings and which
implementation each main-path op took; the last line is one JSON object,
``{"ok": true, "device": {...}}``.  Any failed check raises, so the
process exits non-zero with no such line.  This is a smoke run, not a
benchmark: its times include compilation.
"""
from __future__ import annotations

import json
import math
import os
import sys
import tempfile
import time
from collections import Counter

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "smollm-360m"
SEED = 0
N_CONTEXTS = 4
CALLS_PER_CONTEXT = 6
MAX_NEW = 8
DECODE_BATCH = 4
# The LM head's output is bf16, so the two best logits often tie or
# nearly tie, and two differently shaped programs (paged and batched vs
# plain) may break such a tie either way.  A served token that differs
# from the reference's is accepted only at the first divergence of a
# call, and only when its reference logit is within this of the
# reference's best (the logit tolerance tests/test_models.py holds
# prefill to decode to); the continuations differ from there on.
TIE_TOL = 5e-2


def check(ok: bool, what: str):
    if not ok:
        raise AssertionError(what)


def kv_bytes_per_token(model) -> int:
    spec = model.kv_spec()
    return 2 * model.cfg.n_layers * sum(
        math.prod(spec.leaf_dims[n]) for n in spec.seq_leaves)


def check_kernels(cfg) -> dict:
    """Chunk codec and fused decode attention vs their oracles."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    out = {}
    F = cfg.n_layers * cfg.n_kv_heads * cfg.head_dim      # one chunk row
    x = jax.random.normal(jax.random.PRNGKey(SEED), (16, F), jnp.float32)
    x = (x * 3).astype(jnp.bfloat16)
    for bits in (8, 4, 2):
        p_k, s_k = jax.jit(ops.chunk_quantize, static_argnums=1)(x, bits)
        p_r, s_r = jax.jit(ref.quantize_ref, static_argnums=1)(x, bits)
        check(np.allclose(np.asarray(s_k), np.asarray(s_r), rtol=1e-6,
                          atol=0),
              f"chunk_quant {bits}-bit scales differ from the oracle")
        # codes may differ by one at a rounding boundary (the rule of
        # tests/test_kernels.py for bf16 input); dequantized values
        # then differ by at most one scale step
        d_k = ops.chunk_dequantize(p_k, s_k, bits, 16, jnp.float32)
        d_r = ref.dequantize_ref(p_r, s_r, bits, 16, jnp.float32)
        step = float(np.max(np.asarray(s_r)))
        err = float(np.max(np.abs(np.asarray(d_k) - np.asarray(d_r))))
        check(err <= step * 1.01 + 1e-7,
              f"chunk_quant {bits}-bit round trip off by {err}")
        out[f"chunk_quant_{bits}bit_bytes_differing"] = int(
            np.sum(np.asarray(p_k) != np.asarray(p_r)))

    B, S, H, KV, hd = 4, 512, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(SEED + 1), 8)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, KV, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, KV, hd), jnp.bfloat16)
    kq = jax.random.randint(ks[3], (B, S, KV, hd), -127, 128).astype(jnp.int8)
    vq = jax.random.randint(ks[4], (B, S, KV, hd), -127, 128).astype(jnp.int8)
    kscale = jax.random.uniform(ks[5], (B, S, KV), jnp.float32, 0.001, 0.02)
    vscale = jax.random.uniform(ks[6], (B, S, KV), jnp.float32, 0.001, 0.02)
    qm = jax.random.bernoulli(ks[7], 0.5, (B, S))
    nv = jnp.asarray([S, S - 100, 300, 17], jnp.int32)
    args = (q, k, v, kq, vq, kscale, vscale, qm, nv)
    o_k = jax.jit(ops.decode_mqattn)(*args)
    with jax.default_matmul_precision("highest"):
        o_r = jax.jit(ref.decode_mqattn_ref)(*args)
    err = float(np.max(np.abs(np.asarray(o_k, np.float32)
                              - np.asarray(o_r, np.float32))))
    # tests/test_kernels.py::test_decode_mqattn_matches_ref's tolerance
    check(bool(np.allclose(np.asarray(o_k, np.float32),
                           np.asarray(o_r, np.float32),
                           rtol=2e-2, atol=2e-2)),
          f"decode_mqattn differs from the oracle by {err}")
    out["decode_mqattn_max_abs_err"] = err
    return out


def reference_greedy(prefill, decode, params, prompt, n_new: int):
    """Plain greedy loop over jitted ``model.prefill`` and
    ``model.decode_step`` -> (tokens, logits per emitted token)."""
    import jax.numpy as jnp
    import numpy as np

    out = prefill(params, jnp.asarray(prompt, jnp.int32)[None])
    room = ((0, 0), (0, 0), (0, n_new), (0, 0), (0, 0))
    cache = {n: (jnp.pad(a, room) if n != "pos" else a)
             for n, a in out.cache.items()}
    logits = np.asarray(out.logits[0])
    toks, all_logits = [], []
    for i in range(n_new):
        toks.append(int(np.argmax(logits)))
        all_logits.append(logits)
        if i + 1 < n_new:
            step = decode(params, jnp.asarray([[toks[-1]]], jnp.int32), cache)
            cache, logits = step.cache, np.asarray(step.logits[0])
    return toks, all_logits


def compare_to_reference(served, ref_toks, ref_logits) -> dict:
    """Tokens equal, or equal up to a near-tie (see TIE_TOL) after which
    the two continuations are no longer comparable."""
    check(len(served) == len(ref_toks),
          f"{len(served)} tokens served, reference made {len(ref_toks)}")
    for i, (a, b) in enumerate(zip(served, ref_toks)):
        if a != b:
            gap = float(ref_logits[i][b] - ref_logits[i][a])
            check(gap <= TIE_TOL,
                  f"served token {i} = {a}, reference {b}; reference logit "
                  f"gap {gap} > {TIE_TOL}")
            return {"exact": False, "diverged_at": i, "logit_gap": gap}
    return {"exact": True}


def serve_phase(model, params, events, *, paged: bool, decode_batch: int,
                max_ctx: int, budget: int, profile: bool):
    """Serve ``events`` through LLMService + ServiceRouter -> (stats,
    calls, service-side observations)."""
    from repro.core.restore import io_counters
    from repro.core.scheduler import ServiceRouter
    from repro.core.service import LLMSConfig, LLMService
    from repro.launch.serve import run_trace

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as swap_dir:
        sc = LLMSConfig(policy="llms", max_ctx_len=max_ctx,
                        memory_budget=budget, decode_batch=decode_batch,
                        paged_pool=paged, swap_dir=swap_dir)
        with LLMService(model, params, sc) as svc:
            check(svc.paged == paged, f"paged pool on={svc.paged}")
            if profile:
                svc.profile_pipeline()
            read0 = io_counters()["read"]
            with ServiceRouter(svc, predict=True) as router:
                stats, calls = run_trace(router, events, max_new=MAX_NEW)
            seen = {
                "disk_bytes_read": io_counters()["read"] - read0,
                "chunk_bits": Counter(
                    m.bits for ctx in svc.contexts.values()
                    for m in ctx.chunks.values()),
            }
            if paged:
                seen["impl"] = implementations(svc)
    for ev, s in calls:
        check(s.done and s.error is None,
              f"ctx {ev.ctx_id} call failed: {s.error!r}")
        check(len(s.tokens) == MAX_NEW,
              f"ctx {ev.ctx_id}: {len(s.tokens)} of {MAX_NEW} tokens")
        check(all(0 <= t < model.cfg.vocab for t in s.tokens),
              f"ctx {ev.ctx_id}: token out of vocabulary")
    for key in ("failed_calls", "chunks_corrupt_detected",
                "io_errors_detected", "recover_failed"):
        check(stats[key] == 0, f"{key} = {stats[key]}")
    return stats, calls, seen


def implementations(svc) -> dict:
    """Which implementation the served programs hold for the codec and
    for decode attention: a Pallas kernel lowers to ``tpu_custom_call``."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    exe = svc.exe
    F = exe.n_layers * math.prod(exe.leaf_dims[exe.codec.leaves[0]])
    blk = jax.ShapeDtypeStruct((exe.cs, F), jnp.bfloat16)
    codec_text = exe.codec._q.lower(blk, bits=8).as_text()
    mode = ops._mode(None)
    check(("tpu_custom_call" in codec_text) == (mode == "pallas"),
          f"codec mode {mode!r} but its program does not match")
    B, C = exe.decode_slots, exe.pages_per_ctx
    i32 = jnp.int32
    dec_text = exe.paged_decode_fn.lower(
        exe.params, jax.ShapeDtypeStruct((B, 1), i32), svc.res.pool.arenas,
        jax.ShapeDtypeStruct((B, C), i32), None, None,
        jax.ShapeDtypeStruct((B,), i32)).as_text()
    return {"chunk codec": mode,
            "decode attention": ("pallas" if "tpu_custom_call" in dec_text
                                 else "jnp (models/common.decode_attention:"
                                      " served decode wants density)")}


def serve_smoke(cfg, log=print) -> dict:
    """Every phase at ``cfg``'s widths; raises on any failed check.
    -> what the run observed."""
    import jax
    from repro.models.registry import build_model
    from repro.trace.synth import synthesize_mixed

    report = {"kernels": check_kernels(cfg)}
    log(f"kernels vs oracles: {report['kernels']}")

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    max_ctx = min(2048, cfg.max_seq)
    prompt_len = max_ctx // 8
    ctx_tokens = CALLS_PER_CONTEXT * (prompt_len + MAX_NEW)
    check(ctx_tokens <= max_ctx, "contexts must not condense")
    total_kv = N_CONTEXTS * ctx_tokens * kv_bytes_per_token(model)
    events = synthesize_mixed(
        N_CONTEXTS, N_CONTEXTS * CALLS_PER_CONTEXT, cfg.vocab,
        ctx_pattern="sweep", prompt_len={"dist": "fixed", "n": prompt_len},
        output_len={"dist": "fixed", "n": MAX_NEW}, seed=SEED)
    t_serve = time.perf_counter()
    stats, calls, seen = serve_phase(
        model, params, events, paged=True, decode_batch=DECODE_BATCH,
        max_ctx=max_ctx, budget=total_kv // 8, profile=True)
    t_first = min(s.t_done for _, s in calls) - t_serve
    bits = seen["chunk_bits"]
    check(sum(n for b, n in bits.items() if b < 16) > 0,
          f"chunks by bits {dict(bits)}: none compressed")
    check(seen["disk_bytes_read"] > 0, "no chunk was read back from disk")
    check(stats["router"]["decode_rounds"] > 0, "no decode round ran")
    report["paged"] = {
        "calls": len(calls), "prompt_len": prompt_len, "max_ctx": max_ctx,
        "budget_bytes": total_kv // 8, "contexts_kv_bytes": total_kv,
        "chunks_by_bits": dict(sorted(bits.items())),
        "disk_bytes_read": seen["disk_bytes_read"],
        "disk_bytes_written": stats["disk_bytes_written"],
        "pool_page_faults": stats["pool_page_faults"],
        "decode_rounds": stats["router"]["decode_rounds"],
        "tokens_per_round": (stats["router"]["decoded_tokens"]
                             / stats["router"]["decode_rounds"]),
    }
    report["impl"] = seen["impl"]
    report["first_answer_s"] = t_first
    log(f"paged serving: {report['paged']}")

    first = {}
    for ev, s in calls:
        first.setdefault(ev.ctx_id, (ev, s))
    prefill = jax.jit(lambda p, t: model.prefill(p, {"tokens": t}))
    decode = jax.jit(model.decode_step)
    report["reference"] = {}
    for cid, (ev, s) in sorted(first.items()):
        toks, logits = reference_greedy(prefill, decode, params, ev.prompt,
                                        MAX_NEW)
        report["reference"][cid] = compare_to_reference(s.tokens, toks,
                                                        logits)
    log(f"first calls vs reference: {report['reference']}")

    # slot engine: two contexts alternate under a budget of one chunk,
    # so every switch-in restores; the planner is left unprofiled, and
    # its fallback sends half the missing chunks to recompute and half
    # to disk, which exercises both halves of the pipelined restore
    slot_events = synthesize_mixed(
        2, 6, cfg.vocab, ctx_pattern="sweep",
        prompt_len={"dist": "fixed", "n": prompt_len},
        output_len={"dist": "fixed", "n": MAX_NEW}, seed=SEED + 1)
    stats, _, seen = serve_phase(
        model, params, slot_events, paged=False, decode_batch=1,
        max_ctx=max_ctx, budget=16 * kv_bytes_per_token(model),
        profile=False)
    check(stats["pipelined_restores"] > 0, "no pipelined restore ran")
    check(seen["disk_bytes_read"] > 0, "slot phase read nothing from disk")
    report["slot"] = {"calls": len(slot_events),
                      "pipelined_restores": stats["pipelined_restores"],
                      "disk_bytes_read": seen["disk_bytes_read"]}
    log(f"slot-engine restore: {report['slot']}")
    return report


def main() -> int:
    t0 = time.perf_counter()
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    from repro.launch.serve import use_compile_cache

    cache_dir = use_compile_cache()
    print(f"device: {dev.device_kind} x{len(devices)} ({dev.platform}), "
          f"jax {jax.__version__}, compile cache {cache_dir}", flush=True)
    report = serve_smoke(get_config(ARCH),
                         log=lambda s: print(s, flush=True))
    print(f"implementations: {report['impl']}")
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    print("peak device memory: "
          + (f"{peak / 2**30:.2f} GiB" if peak else "not reported"))
    print(f"first answered call: {report['first_answer_s']} s after the "
          "start of serving (compiles included)")
    print(f"wall: {time.perf_counter() - t0} s (compiles included; "
          "a smoke run, not a benchmark)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
