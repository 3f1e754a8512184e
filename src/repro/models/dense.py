"""Dense decoder-only transformer (llama / qwen / opt / smollm family).

Covers: llama2-7b, opt-6.7b (paper's models), deepseek-67b, qwen3-32b,
qwen2.5-14b, smollm-360m.  Optional qk-norm (qwen3) and qkv-bias (qwen2.5).

Layer params are stacked (L, ...) and consumed by ``lax.scan``; training
wraps the layer body in ``jax.checkpoint``.  The LM head loss is computed
in sequence blocks so (B, S, vocab) logits are never materialized.

This module also implements the paper's **interleaved-chunk recompute**
entry (`recompute`): given a KV cache with holes and the original tokens
of the missing slots, it recomputes exactly those positions with global
RoPE and an iota-built ``k_pos <= q_pos`` mask (paper Fig. 7), reusing
the same layer weights/scan.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import io_callback

from repro.models import common as C
from repro.models.api import DecodeOut, ModelBase, PrefillOut
from repro.models.kvspec import KVSpec, LAYOUT_MIXED, LAYOUT_WINDOW

Array = jax.Array


# --------------------------------------------------------------------- #
# Blockwise LM head CE: never materializes (B, S, V)
# --------------------------------------------------------------------- #
def blockwise_ce(x: Array, head: Array, targets: Array,
                 mask: Optional[Array] = None, block: int = 512
                 ) -> Tuple[Array, Dict[str, Array]]:
    """x: (B,S,d) final hidden; head: (d,V); targets (B,S)."""
    B, S, d = x.shape
    nb = (S + block - 1) // block
    pad = nb * block - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        m = jnp.pad(jnp.ones((B, S), jnp.float32) if mask is None
                    else mask.astype(jnp.float32), ((0, 0), (0, pad)))
    else:
        m = jnp.ones((B, S), jnp.float32) if mask is None else mask.astype(jnp.float32)
    xb = x.reshape(B, nb, block, d).transpose(1, 0, 2, 3)
    tb = targets.reshape(B, nb, block).transpose(1, 0, 2)
    mb = m.reshape(B, nb, block).transpose(1, 0, 2)

    def step(carry, inp):
        nll_sum, acc_sum, cnt = carry
        xx, tt, mm = inp
        logits = (xx @ head).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, tt[..., None], axis=-1)[..., 0]
        nll_sum = nll_sum + jnp.sum((logz - ll) * mm)
        acc_sum = acc_sum + jnp.sum((jnp.argmax(logits, -1) == tt) * mm)
        return (nll_sum, acc_sum, cnt + jnp.sum(mm)), None

    (nll, acc, cnt), _ = jax.lax.scan(
        step, (jnp.float32(0), jnp.float32(0), jnp.float32(0)), (xb, tb, mb))
    cnt = jnp.maximum(cnt, 1.0)
    loss = nll / cnt
    return loss, {"loss": loss, "acc": acc / cnt}


# the mixed-precision (quant-resident) cache leaves that ride along the
# bf16 k/v through every entry point but are never written by them
_QUANT_LEAVES = ("k_q", "v_q", "k_scale", "v_scale")


def _quant_scan_xs(cache, xs):
    """Append the per-layer quant-segment leaves to a layer-scan input."""
    return xs + tuple(cache[n] for n in _QUANT_LEAVES)


def _carry_quant_leaves(new_cache, cache, qm):
    """Decode/recompute never write the quant segments: alias them (and
    the updated quant mask) into the output cache."""
    for n in _QUANT_LEAVES:
        new_cache[n] = cache[n]
    new_cache["quant_mask"] = qm
    return new_cache


def _inner_group(L: int) -> int:
    """Divisor of L nearest sqrt(L) (inner layer count for 2-level remat)."""
    best, target = L, L ** 0.5
    for k in range(1, L + 1):
        if L % k == 0 and abs(k - target) < abs(best - target):
            best = k
    return best


class DenseModel(ModelBase):

    def kv_spec(self) -> KVSpec:
        cfg = self.cfg
        kv_dims = (cfg.n_kv_heads, cfg.head_dim)
        return KVSpec(
            family=cfg.family,
            seq_leaves=("k", "v"),
            leaf_dims={"k": kv_dims, "v": kv_dims},
            servable=True,
            chunkable=True,
            recomputable=True,
            batched_decode=True,
            quant_resident=True,
            paged=True,
            pipelined_restore=True,
            layouts=(LAYOUT_WINDOW, LAYOUT_MIXED),
            tolerance_class="kv",
            min_bits=2,
            int8_serving=True,
            streaming_long=True,
        )

    # ------------------------------------------------------------------ #
    def init(self, key) -> Dict:
        cfg = self.cfg
        H, KV, hd, d, ff = (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
                            cfg.d_model, cfg.d_ff)
        L = cfg.n_layers
        ks = jax.random.split(key, 16)
        lin = C.init_linear
        layers = {
            "ln_attn": jnp.ones((L, d), jnp.float32),
            "ln_ffn": jnp.ones((L, d), jnp.float32),
            "wq": lin(ks[0], (L, d, H * hd)),
            "wk": lin(ks[1], (L, d, KV * hd)),
            "wv": lin(ks[2], (L, d, KV * hd)),
            "wo": lin(ks[3], (L, H * hd, d)),
            "w_gate": lin(ks[4], (L, d, ff)),
            "w_up": lin(ks[5], (L, d, ff)),
            "w_down": lin(ks[6], (L, ff, d)),
        }
        if cfg.qkv_bias:
            layers["bq"] = jnp.zeros((L, H * hd), jnp.float32)
            layers["bk"] = jnp.zeros((L, KV * hd), jnp.float32)
            layers["bv"] = jnp.zeros((L, KV * hd), jnp.float32)
        if cfg.qk_norm:
            layers["q_norm"] = jnp.ones((L, hd), jnp.float32)
            layers["k_norm"] = jnp.ones((L, hd), jnp.float32)
        params = {
            "embed": lin(ks[7], (cfg.vocab, d)),
            "ln_f": jnp.ones((d,), jnp.float32),
            "layers": layers,
        }
        if not cfg.tie_embeddings:
            params["head"] = lin(ks[8], (d, cfg.vocab))
        return params

    def head_weight(self, params) -> Array:
        if self.cfg.tie_embeddings:
            return params["embed"].T
        return params["head"]

    # -- per-layer pieces ---------------------------------------------- #
    def _qkv(self, pl, h):
        cfg = self.cfg
        B, S, _ = h.shape
        q = h @ pl["wq"]
        k = h @ pl["wk"]
        v = h @ pl["wv"]
        if cfg.qkv_bias:
            q = q + pl["bq"].astype(q.dtype)
            k = k + pl["bk"].astype(k.dtype)
            v = v + pl["bv"].astype(v.dtype)
        q = q.reshape(B, S, cfg.n_heads, cfg.head_dim)
        k = k.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
        v = v.reshape(B, S, cfg.n_kv_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = C.rms_norm(q, pl["q_norm"], cfg.norm_eps)
            k = C.rms_norm(k, pl["k_norm"], cfg.norm_eps)
        return q, k, v

    def _rope(self, q, k, positions):
        cfg = self.cfg
        cos, sin = C.rope_angles(positions, cfg.head_dim, cfg.rope_theta)
        return C.apply_rope(q, cos, sin), C.apply_rope(k, cos, sin)

    def _ffn(self, pl, x):
        h = C.rms_norm(x, pl["ln_ffn"], self.cfg.norm_eps)
        return x + C.swiglu(h, pl["w_gate"], pl["w_up"], pl["w_down"])

    # -- full-sequence layer (train / prefill) -------------------------- #
    def _layer_full(self, pl, x, positions, window, n_sinks, want_density,
                    return_kv):
        h = C.rms_norm(x, pl["ln_attn"], self.cfg.norm_eps)
        q, k, v = self._qkv(pl, h)
        q, k = self._rope(q, k, positions)
        S = x.shape[1]
        if (S > 2048 or window) and not want_density:
            out = C.flash_attention(q, k, v, 0, 1024, window, n_sinks)
            ao = C.AttnOut(out, None)
        elif S > 2048 or window:
            ao = C.blocked_causal_attention(
                q, k, v, q_offset=0, block=1024, window=window,
                n_sinks=n_sinks, want_density=want_density)
        else:
            mask = C.causal_window_mask(positions, positions, window, n_sinks)
            ao = C.gqa_attention(q, k, v, mask, want_density=want_density)
        x = x + ao.out.reshape(*x.shape[:2], -1) @ pl["wo"]
        x = self._ffn(pl, x)
        extras = {}
        if want_density:
            extras["density"] = ao.key_density
        if return_kv:
            extras["k"], extras["v"] = k, v
        return x, extras

    def _stack_full(self, params, tokens, *, window=0, n_sinks=0,
                    want_density=False, return_kv=False, remat=False):
        cfg = self.cfg
        x = C.constrain_batch(params["embed"][tokens].astype(jnp.bfloat16))
        S = tokens.shape[1]
        positions = jax.lax.broadcasted_iota(jnp.int32, (S,), 0)

        def body(x, pl):
            x, extras = self._layer_full(pl, x, positions, window, n_sinks,
                                         want_density, return_kv)
            return C.constrain_batch(x), extras

        L = cfg.n_layers
        if remat and L >= 16:
            # two-level remat: scan over layer GROUPS (group inputs saved)
            # with a checkpointed per-layer body inside — peak residency is
            # G + k activations plus ONE layer's transients, instead of L.
            k = _inner_group(L)
            G = L // k
            grouped = jax.tree.map(
                lambda a: a.reshape(G, k, *a.shape[1:]), params["layers"])
            inner = jax.checkpoint(body)

            def group(x, pg):
                return jax.lax.scan(inner, x, pg)

            x, extras = jax.lax.scan(jax.checkpoint(group), x, grouped)
            extras = jax.tree.map(
                lambda a: a.reshape(G * k, *a.shape[2:]), extras)
        else:
            if remat:
                body = jax.checkpoint(body)
            x, extras = jax.lax.scan(body, x, params["layers"])
        x = C.rms_norm(x, params["ln_f"], cfg.norm_eps)
        return x, extras

    # -- entry points ---------------------------------------------------- #
    def loss(self, params, batch):
        x, _ = self._stack_full(params, batch["tokens"], remat=True)
        return blockwise_ce(x, self.head_weight(params), batch["targets"],
                            batch.get("mask"))

    def prefill(self, params, batch, want_density=False, window=0, n_sinks=0):
        tokens = batch["tokens"]
        x, extras = self._stack_full(
            params, tokens, window=window, n_sinks=n_sinks,
            want_density=want_density, return_kv=True)
        logits = (x[:, -1] @ self.head_weight(params)).astype(jnp.float32)
        cache = {
            "k": extras["k"],            # (L, B, S, KV, hd)
            "v": extras["v"],
            "pos": jnp.int32(tokens.shape[1]),
        }
        density = None
        if want_density:
            density = jnp.mean(extras["density"], axis=0)   # (B, S) over layers
        return PrefillOut(logits, cache, density)

    def decode_step(self, params, tokens, cache, window=0, n_sinks=0,
                    want_density=False, unroll: int = 1):
        """``unroll`` feeds ``lax.scan(..., unroll=)`` over the layers.
        The batched decode entry passes the full layer count: XLA CPU's
        rolled scan emits per-iteration buffer shuffles that dominate a
        multi-row step (~5x on the bench model), while the unrolled body
        fuses clean.  The serial (B=1) path keeps the rolled scan — its
        one-layer-sized HLO — and is numerically unaffected either way."""
        cfg = self.cfg
        x = C.constrain_batch(
            params["embed"][tokens].astype(jnp.bfloat16))  # (B, 1, d)
        pos = cache["pos"]
        # scalar pos: all rows decode at one position (serial working
        # cache).  (B,) pos: per-row slot positions (batched decode) —
        # rope needs a (B, 1) position table so each row rotates at its
        # own offset.
        positions = pos[None] if pos.ndim == 0 else pos[:, None]

        mixed = "k_q" in cache               # bf16 window + int8 segments
        quantized = "k_scale" in cache and not mixed   # all-int8 cache

        if mixed:
            # the new token lands in the bf16 window: clear its
            # quant-mask bit once (the mask is shared across layers)
            S = cache["k"].shape[2]
            s_pos = jax.lax.broadcasted_iota(jnp.int32, (S,), 0)
            idx = pos[None] if pos.ndim == 0 else pos
            qm = cache["quant_mask"] & ~(s_pos[None, :] == idx[:, None])[None]

        def body(x, layer_in):
            kq_c = vq_c = ks_c = vs_c = None
            if mixed:
                pl, k_c, v_c, kq_c, vq_c, ks_c, vs_c = layer_in
            elif quantized:
                pl, k_c, v_c, ks_c, vs_c = layer_in
            else:
                pl, k_c, v_c = layer_in
            h = C.rms_norm(x, pl["ln_attn"], cfg.norm_eps)
            q, k, v = self._qkv(pl, h)
            q, k = self._rope(q, k, positions)
            # keep heads replicated so the SEQUENCE-sharded cache is never
            # re-gathered: attention runs S-local with a tiny partial-
            # softmax all-reduce (flash-decoding; EXPERIMENTS.md §Perf)
            q, k, v = (C.constrain_batch(t) for t in (q, k, v))
            if quantized:
                # per-(token, kv-head) symmetric scales; the attention
                # kernel dequantizes in VMEM (kernels/decode_qattn.py)
                ks = jnp.max(jnp.abs(k.astype(jnp.float32)), -1) / 127.0
                vs = jnp.max(jnp.abs(v.astype(jnp.float32)), -1) / 127.0
                ks = jnp.maximum(ks, 1e-8)
                vs = jnp.maximum(vs, 1e-8)
                kq = jnp.clip(jnp.round(k / ks[..., None]), -127, 127
                              ).astype(jnp.int8)
                vq = jnp.clip(jnp.round(v / vs[..., None]), -127, 127
                              ).astype(jnp.int8)
                k_c = C.ring_update(k_c, kq, pos)
                v_c = C.ring_update(v_c, vq, pos)
                ks_c = C.ring_update(ks_c, ks, pos)
                vs_c = C.ring_update(vs_c, vs, pos)
                out = C.decode_attention(q, k_c, v_c, pos + 1,
                                         k_scale=ks_c, v_scale=vs_c,
                                         window=window, n_sinks=n_sinks,
                                         want_density=want_density)
            elif mixed:
                k_c = C.ring_update(k_c, k, pos)
                v_c = C.ring_update(v_c, v, pos)
                out = C.mixed_decode_attention(
                    q, k_c, v_c, kq_c, vq_c, ks_c, vs_c, qm[0], pos + 1,
                    window=window, n_sinks=n_sinks,
                    want_density=want_density)
            else:
                k_c = C.ring_update(k_c, k, pos)
                v_c = C.ring_update(v_c, v, pos)
                out = C.decode_attention(q, k_c, v_c, pos + 1,
                                         window=window, n_sinks=n_sinks,
                                         want_density=want_density)
            ys = {"k": k_c, "v": v_c}
            if quantized:
                ys["k_scale"], ys["v_scale"] = ks_c, vs_c
            if want_density:
                out, mass = out
                ys["mass"] = mass
            x = x + out.reshape(*x.shape[:2], -1) @ pl["wo"]
            x = C.constrain_batch(self._ffn(pl, x))
            return x, ys

        xs = (params["layers"], cache["k"], cache["v"])
        if mixed:
            xs = _quant_scan_xs(cache, xs)
        elif quantized:
            xs = xs + (cache["k_scale"], cache["v_scale"])
        x, ys = jax.lax.scan(body, x, xs, unroll=max(1, int(unroll)))
        x = C.rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = (x[:, 0] @ self.head_weight(params)).astype(jnp.float32)
        new_cache = {"k": ys["k"], "v": ys["v"], "pos": pos + 1}
        if mixed:
            _carry_quant_leaves(new_cache, cache, qm)
        elif quantized:
            new_cache["k_scale"] = ys["k_scale"]
            new_cache["v_scale"] = ys["v_scale"]
        out = DecodeOut(logits, new_cache)
        if want_density:
            return out, jnp.mean(ys["mass"], axis=0)        # (B, S)
        return out

    def _build_cache(self, batch, seq, dtype, layout):
        cfg = self.cfg
        shape = (cfg.n_layers, batch, seq, cfg.n_kv_heads, cfg.head_dim)
        cache = {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype),
                 "pos": jnp.int32(0)}
        if dtype == jnp.int8:       # quantized serving cache (+ scales)
            cache["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
            cache["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
        elif layout == LAYOUT_MIXED:
            # mixed-precision working cache: bf16 recent window + int8
            # quant-resident chunk segments with per-(token, kv-head)
            # scales, selected per position by quant_mask.  The mask
            # carries a dummy leading axis so axis 1 is the batch axis
            # for every leaf (the paged gather stacks rows on axis 1).
            cache["k_q"] = jnp.zeros(shape, jnp.int8)
            cache["v_q"] = jnp.zeros(shape, jnp.int8)
            cache["k_scale"] = jnp.zeros(shape[:-1], jnp.float32)
            cache["v_scale"] = jnp.zeros(shape[:-1], jnp.float32)
            cache["quant_mask"] = jnp.zeros((1, batch, seq), bool)
        return cache

    # ------------------------------------------------------------------ #
    # Paper Fig. 7: recompute missing chunks at scattered positions.
    # ------------------------------------------------------------------ #
    def recompute(self, params, miss_tokens: Array, miss_pos: Array,
                  cache, seq_len, window: int = 0, n_sinks: int = 0,
                  want_density: bool = False):
        """miss_tokens: (B, M) original text of the missing slots;
        miss_pos: (M,) absolute positions; cache: KV with holes at those
        positions; seq_len: number of valid context tokens INCLUDING the
        missing ones.  Returns (cache', hidden (B,M,d), density (B,S)|None)
        — the cache with the missing K/V recomputed exactly (global RoPE
        + on-the-fly causal mask, attending over resident + recomputed KV).

        This same entry point serves as the chunked **prefill-append**:
        append T new tokens by passing miss_pos = [S0, S0+T) against a
        cache holding the first S0 tokens.
        """
        cfg = self.cfg
        x = params["embed"][miss_tokens].astype(jnp.bfloat16)    # (B, M, d)
        S = cache["k"].shape[2]
        k_pos_all = jax.lax.broadcasted_iota(jnp.int32, (S,), 0)
        mixed = "k_q" in cache
        if mixed:
            # recomputed positions land in the bf16 window; resident
            # quant segments are read THROUGH during attention (mixed-
            # precision prefill-read; quant-resident prefill-WRITE is a
            # deferred open item, ROADMAP.md)
            qm = cache["quant_mask"] & ~jnp.any(
                k_pos_all[None, :] == miss_pos[:, None], axis=0)[None, None]

        def body(x, layer_in):
            kq_c = vq_c = ks_c = vs_c = None
            if mixed:
                pl, k_c, v_c, kq_c, vq_c, ks_c, vs_c = layer_in
            else:
                pl, k_c, v_c = layer_in
            h = C.rms_norm(x, pl["ln_attn"], cfg.norm_eps)
            q, k, v = self._qkv(pl, h)
            q, k = self._rope(q, k, miss_pos)
            # scatter the recomputed K/V into the resident cache
            k_c = k_c.at[:, miss_pos].set(k.astype(k_c.dtype))
            v_c = v_c.at[:, miss_pos].set(v.astype(v_c.dtype))
            if mixed:
                k_att = C.dequant_select(k_c, kq_c, ks_c, qm[0])
                v_att = C.dequant_select(v_c, vq_c, vs_c, qm[0])
            else:
                k_att, v_att = k_c, v_c
            # attend: q at miss_pos over all valid tokens <= its position
            mask = C.causal_window_mask(miss_pos, k_pos_all, window, n_sinks)
            mask = mask & (k_pos_all < seq_len)[None, :]
            ao = C.gqa_attention(q, k_att.astype(q.dtype),
                                 v_att.astype(q.dtype),
                                 mask, want_density=want_density)
            x = x + ao.out.reshape(*x.shape[:2], -1) @ pl["wo"]
            x = C.constrain_batch(self._ffn(pl, x))
            ys = {"k": k_c, "v": v_c}
            if want_density:
                ys["density"] = ao.key_density
            return x, ys

        xs = (params["layers"], cache["k"], cache["v"])
        if mixed:
            xs = _quant_scan_xs(cache, xs)
        x, ys = jax.lax.scan(body, x, xs)
        x = C.rms_norm(x, params["ln_f"], cfg.norm_eps)
        density = jnp.mean(ys["density"], axis=0) if want_density else None
        new_cache = {"k": ys["k"], "v": ys["v"], "pos": cache["pos"]}
        if mixed:
            _carry_quant_leaves(new_cache, cache, qm)
        return new_cache, x, density

    # ------------------------------------------------------------------ #
    # Paged KV pool entries: decode/prefill directly over page arenas.
    # Both gather the per-slot page rows into the SAME dense mixed-cache
    # layout the slot entries consume, run the unchanged decode_step /
    # recompute body, and scatter only the newly written tokens back
    # into their bf16 tail pages — so slots are views into the pool and
    # the emitted tokens are bit-identical to the slot-cache path.  On
    # the chip, decode reads bf16 pages in place instead where
    # ``C.paged_decode_in_place`` says so (same mask, f32 online softmax).
    # ------------------------------------------------------------------ #
    def decode_paged(self, params, tokens, arenas, pt16, pt8, quant_chunks,
                     pos, window: int = 0, n_sinks: int = 0,
                     want_density: bool = False, unroll: int = 1):
        """One [B, 1] decode round over the pool.  tokens (B, 1);
        pt16/pt8 (B, C) page-table rows; quant_chunks (B, C) bool (None
        with pt8=None outside quant-resident mode); pos (B,) per-slot
        decode positions.  -> (arenas', logits[, mass][, experts]).
        Batch membership is carried entirely by the page-table rows:
        joining or leaving the batch changes only pt16/pt8/pos, never
        copies cache state (no merge/split).  Where
        ``C.paged_decode_in_place`` holds, the layers read the pages in
        place and no dense view is built; otherwise they run
        ``decode_step`` over ``paged_cache_view``, for whatever cache
        leaves the family's spec declares.  A family whose decode step
        returns ``DecodeOut.experts`` also returns those (L_moe, B, k)
        ids, pad rows included."""
        leaves = self.kv_spec().seq_leaves
        cs = arenas[leaves[0] + "16"].shape[2]
        rows = jnp.arange(tokens.shape[0])
        experts = None
        if C.paged_decode_in_place(arenas, pt8):
            logits, new_kv, mass = self._decode_in_place(
                params, tokens, arenas, pt16, pos, window, n_sinks,
                want_density, unroll)
        else:
            cache = C.paged_cache_view(arenas, leaves, pt16, pt8,
                                       quant_chunks, pos)
            out = self.decode_step(params, tokens, cache, window, n_sinks,
                                   want_density, unroll)
            mass = None
            if want_density:
                out, mass = out
            logits, experts, new_kv = out.logits, out.experts, out.new_kv
            if new_kv is None:
                new_kv = {n: out.cache[n][:, rows, pos] for n in leaves}
        # the round's new tokens, (L, B, ...) a leaf, in one scatter
        pages = pt16[rows, pos // cs]
        offs = pos % cs
        new_arenas = dict(arenas)
        for n in leaves:
            new_arenas[n + "16"] = arenas[n + "16"].at[
                :, pages, offs].set(new_kv[n])
        outs = (new_arenas, logits) + ((mass,) if want_density else ())
        return outs + (() if experts is None else (experts,))

    def _decode_in_place(self, params, tokens, arenas, pt16, pos, window,
                         n_sinks, want_density, unroll):
        """``decode_step``'s layer scan over pages read in place
        (``C.paged_decode_attention``): the scan's ``ys`` carry only the
        round's new K/V and the mass, never a cache.
        -> (logits, {"k", "v"}: (L, B, KV, hd), mass (B, S) | None)."""
        cfg = self.cfg
        x = C.constrain_batch(
            params["embed"][tokens].astype(jnp.bfloat16))  # (B, 1, d)
        positions = pos[:, None]
        k_ar, v_ar = arenas["k16"], arenas["v16"]

        def body(x, layer_in):
            pl, layer = layer_in
            h = C.rms_norm(x, pl["ln_attn"], cfg.norm_eps)
            q, k, v = self._qkv(pl, h)
            q, k = self._rope(q, k, positions)
            q, k, v = (C.constrain_batch(t) for t in (q, k, v))
            out = C.paged_decode_attention(
                q, k, v, k_ar, v_ar, layer, pt16, pos, window=window,
                n_sinks=n_sinks, want_density=want_density)
            ys = {"k": k[:, 0].astype(k_ar.dtype),
                  "v": v[:, 0].astype(v_ar.dtype)}
            if want_density:
                out, ys["mass"] = out
            x = x + out.reshape(*x.shape[:2], -1) @ pl["wo"]
            x = C.constrain_batch(self._ffn(pl, x))
            return x, ys

        xs = (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32))
        x, ys = jax.lax.scan(body, x, xs, unroll=max(1, int(unroll)))
        x = C.rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = (x[:, 0] @ self.head_weight(params)).astype(jnp.float32)
        mass = jnp.mean(ys["mass"], axis=0) if want_density else None
        return logits, ys, mass

    def extend_paged(self, params, miss_tokens, miss_pos, arenas, pt16,
                     pt8, quant_chunks, seq_len, window: int = 0,
                     n_sinks: int = 0, want_density: bool = False):
        """Chunked prefill-append over the pool (B = 1): the paged form
        of ``recompute``'s append mode.  miss_pos positions must map to
        bf16 pages already allocated in pt16 (padding positions map to
        the scratch page 0).  -> (arenas', hidden (1, M, d), density)."""
        leaves = self.kv_spec().seq_leaves
        cs = arenas[leaves[0] + "16"].shape[2]
        cache = C.paged_cache_view(arenas, leaves, pt16, pt8,
                                   quant_chunks, jnp.int32(0))
        new_cache, x, density = self.recompute(
            params, miss_tokens, miss_pos, cache, seq_len, window,
            n_sinks, want_density)
        pages = pt16[0, miss_pos // cs]
        offs = miss_pos % cs
        new_arenas = dict(arenas)
        for n in leaves:
            val = new_cache[n][:, 0, miss_pos]          # (L, M, ...)
            new_arenas[n + "16"] = arenas[n + "16"].at[
                :, pages, offs].set(val)
        return new_arenas, x, density

    # ------------------------------------------------------------------ #
    # Paper Fig. 8: swapping-recompute PIPELINED restore.  The scan body
    # pulls layer l's disk-loaded chunk K/V through an ordered
    # io_callback while recomputing the complementary chunk set — the
    # I/O thread (core/restore.py LayerFeed) runs one layer ahead.
    # ------------------------------------------------------------------ #
    def recompute_pipelined(self, params, miss_tokens: Array,
                            miss_pos: Array, io_pos: Array, cache, seq_len,
                            fetch, window: int = 0, n_sinks: int = 0,
                            want_density: bool = False):
        """miss_*: chunks restored by recompute; io_pos (Mio,): token
        positions of chunks arriving from disk, fetched per layer via
        ``fetch(layer) -> {leaf: (Mio, KV, hd) fp32}``."""
        cfg = self.cfg
        x = params["embed"][miss_tokens].astype(jnp.bfloat16)
        S = cache["k"].shape[2]
        Mio = io_pos.shape[0]
        k_pos_all = jax.lax.broadcasted_iota(jnp.int32, (S,), 0)
        io_shape = {
            "k": jax.ShapeDtypeStruct(
                (Mio, cfg.n_kv_heads, cfg.head_dim), jnp.float32),
            "v": jax.ShapeDtypeStruct(
                (Mio, cfg.n_kv_heads, cfg.head_dim), jnp.float32),
        }
        mixed = "k_q" in cache
        if mixed:
            # recomputed AND disk-restored positions materialize in the
            # bf16 window; surviving quant segments are read through
            restored = (jnp.any(k_pos_all[None, :] == miss_pos[:, None], 0)
                        | jnp.any(k_pos_all[None, :] == io_pos[:, None], 0))
            qm = cache["quant_mask"] & ~restored[None, None]

        def body(x, layer_in):
            kq_c = vq_c = ks_c = vs_c = None
            if mixed:
                l_idx, pl, k_c, v_c, kq_c, vq_c, ks_c, vs_c = layer_in
            else:
                l_idx, pl, k_c, v_c = layer_in
            io = io_callback(fetch, io_shape, l_idx, ordered=True)
            k_c = k_c.at[:, io_pos].set(io["k"][None].astype(k_c.dtype))
            v_c = v_c.at[:, io_pos].set(io["v"][None].astype(v_c.dtype))
            h = C.rms_norm(x, pl["ln_attn"], cfg.norm_eps)
            q, k, v = self._qkv(pl, h)
            q, k = self._rope(q, k, miss_pos)
            k_c = k_c.at[:, miss_pos].set(k.astype(k_c.dtype))
            v_c = v_c.at[:, miss_pos].set(v.astype(v_c.dtype))
            if mixed:
                k_att = C.dequant_select(k_c, kq_c, ks_c, qm[0])
                v_att = C.dequant_select(v_c, vq_c, vs_c, qm[0])
            else:
                k_att, v_att = k_c, v_c
            mask = C.causal_window_mask(miss_pos, k_pos_all, window, n_sinks)
            mask = mask & (k_pos_all < seq_len)[None, :]
            ao = C.gqa_attention(q, k_att.astype(q.dtype),
                                 v_att.astype(q.dtype),
                                 mask, want_density=want_density)
            x = x + ao.out.reshape(*x.shape[:2], -1) @ pl["wo"]
            x = C.constrain_batch(self._ffn(pl, x))
            ys = {"k": k_c, "v": v_c}
            if want_density:
                ys["density"] = ao.key_density
            return x, ys

        layer_ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        xs = (layer_ids, params["layers"], cache["k"], cache["v"])
        if mixed:
            xs = _quant_scan_xs(cache, xs)
        x, ys = jax.lax.scan(body, x, xs)
        x = C.rms_norm(x, params["ln_f"], cfg.norm_eps)
        density = jnp.mean(ys["density"], axis=0) if want_density else None
        new_cache = {"k": ys["k"], "v": ys["v"], "pos": cache["pos"]}
        if mixed:
            _carry_quant_leaves(new_cache, cache, qm)
        return new_cache, x, density
