"""DeepSeek-V2 style Multi-head Latent Attention + MoE (deepseek-v2-lite).

MLA caches a single compressed latent per token — ``ckv`` (kv_lora_rank)
plus a shared roped key ``kpe`` (qk_rope_head_dim) — instead of per-head
K/V.  Prefill uses the naive up-projection form (efficient when S tokens
share the up-projection); decode uses the **absorbed** form (q is folded
through W_uk, attention runs directly against the rank-512 latent), the
standard MLA serving trick.

The first ``moe.first_dense`` layers run a dense SwiGLU MLP (their
params are the ``dense`` group, ahead of the ``layers`` group of routed
layers, each group scanned on its own); every other layer runs the
dropless expert layer (``models/moe_layer.py``).  The roped part takes
YaRN scaling as DeepSeek-V2's modeling code does: blended inverse
frequencies, and a softmax scale of ``qk_head_dim^-0.5 * mscale^2``
(folded into the queries).  Rope pairs are (x[i], x[i + d/2]): the
published code de-interleaves its (x[2i], x[2i+1]) pairs into this
order before rotating, so the two differ by a fixed permutation of the
rope columns of ``wq`` and ``w_dkv``.

LLMS applicability: chunks store (ckv, kpe) slices — the paper's
compression/swapping applies to the latent directly, and ``recompute``
restores missing latent chunks exactly (global RoPE on kpe; routing is
per token, so no other context changes a latent).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MLAConfig
from repro.models import common as C
from repro.models.api import DecodeOut, PrefillOut
from repro.models.dense import DenseModel
from repro.models.kvspec import KVSpec, LAYOUT_MIXED, LAYOUT_WINDOW
from repro.models.moe_layer import init_moe_params, moe_ffn

Array = jax.Array

# mixed-precision (quant-resident) latent leaves: int8 codes + per-
# (token, layer) scales over the whole rank vector, riding along the
# bf16 window exactly like dense's k_q/v_q tier
_LATENT_QUANT_LEAVES = ("ckv_q", "kpe_q", "ckv_scale", "kpe_scale")
_MOE_KEYS = ("router", "w_gate", "w_up", "w_down", "s_gate", "s_up",
             "s_down")
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def yarn_get_mscale(scale: float, mscale: float) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(m: MLAConfig, theta: float) -> np.ndarray:
    """(qk_rope_head_dim // 2,) inverse frequencies: the plain ones
    below the correction range of ``beta_fast``, divided by ``factor``
    above that of ``beta_slow``, blended linearly between."""
    dim = m.qk_rope_head_dim
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if m.factor <= 1:
        return extra.astype(np.float32)
    orig = m.original_max_position_embeddings

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(corr(m.beta_fast)), 0)
    high = min(math.ceil(corr(m.beta_slow)), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3),
                   0, 1)
    mask = 1.0 - ramp
    return (extra / m.factor * (1 - mask) + extra * mask).astype(np.float32)


def softmax_scale(m: MLAConfig) -> float:
    s = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    if m.mscale_all_dim:
        s *= yarn_get_mscale(m.factor, m.mscale_all_dim) ** 2
    return s


def _latent_select(c, q, s, qm0):
    """Per-position select between the bf16 window and the dequantized
    int8 resident segment.  c (B,S,r) bf16; q (B,S,r) int8; s (B,S)
    fp32; qm0 (B,S) bool.  Matches the residency dequantize path
    bit-for-bit at 8-bit (codes * scale, rounded once to c.dtype)."""
    deq = (q.astype(jnp.float32) * s[..., None]).astype(c.dtype)
    return jnp.where(qm0[..., None], deq, c)


def _write_token(leaf, new, pos):
    """The cache leaf (L, B, S, d) with each row's new token (L, B, d)
    written at ``pos``: one position for all rows (scalar) or each
    row's own ((B,))."""
    if pos.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(
            leaf, new[:, :, None].astype(leaf.dtype), pos, axis=2)
    S = leaf.shape[2]
    at = jax.lax.broadcasted_iota(jnp.int32, (S,), 0)[None, :] == pos[:, None]
    return jnp.where(at[None, :, :, None], new[:, :, None].astype(leaf.dtype),
                     leaf)


def _concat_groups(outs):
    """Per-group scan outputs -> one (L, ...) leaf per key.  Keys in
    first-seen order: a set's order follows the process's string hash
    seed, and would give each process its own program text (so its own
    compile-cache key)."""
    keys = dict.fromkeys(k for o in outs for k in o)
    return {k: jnp.concatenate([o[k] for o in outs if k in o], axis=0)
            for k in keys}


class MLAModel(DenseModel):

    def kv_spec(self) -> KVSpec:
        cfg, m = self.cfg, self.cfg.mla
        return KVSpec(
            family=cfg.family,
            seq_leaves=("ckv", "kpe"),
            leaf_dims={"ckv": (m.kv_lora_rank,),
                       "kpe": (m.qk_rope_head_dim,)},
            servable=True,
            chunkable=True,
            recomputable=True,
            batched_decode=True,
            quant_resident=True,
            paged=True,
            pipelined_restore=False,
            layouts=(LAYOUT_WINDOW, LAYOUT_MIXED),
            # the rank-512 latent carries no cross-head redundancy: the
            # Eq.-3 planner stops at 8-bit (where dense K/V may drop to
            # 4/2), so every swapped chunk is quant-resident eligible
            tolerance_class="latent",
            min_bits=8,
            streaming_long=True,
        )

    def _groups(self):
        """(params key, first layer, end layer, routed) per layer group,
        in layer order; empty groups left out."""
        L, nd = self.cfg.n_layers, self.cfg.moe.first_dense
        return tuple(g for g in (("dense", 0, nd, False),
                                 ("layers", nd, L, True)) if g[2] > g[1])

    def init(self, key):
        cfg = self.cfg
        m, moe = cfg.mla, cfg.moe
        assert m is not None and moe is not None
        d, H = cfg.d_model, cfg.n_heads
        qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
        lin = C.init_linear
        ks = jax.random.split(key, 3)
        params = {
            "embed": lin(ks[0], (cfg.vocab, d)),
            "head": lin(ks[1], (d, cfg.vocab)),
            "ln_f": jnp.ones((d,), jnp.float32),
        }
        for name, lo, hi, routed in self._groups():
            n = hi - lo
            kg = jax.random.split(jax.random.fold_in(ks[2], lo), 9)
            g = {
                "ln_attn": jnp.ones((n, d), jnp.float32),
                "ln_ffn": jnp.ones((n, d), jnp.float32),
                "ln_kv": jnp.ones((n, m.kv_lora_rank), jnp.float32),
                "wq": lin(kg[0], (n, d, H * qk_hd)),
                "w_dkv": lin(kg[1], (n, d, m.kv_lora_rank
                                     + m.qk_rope_head_dim)),
                "w_uk": lin(kg[2], (n, m.kv_lora_rank,
                                    H * m.qk_nope_head_dim)),
                "w_uv": lin(kg[3], (n, m.kv_lora_rank, H * m.v_head_dim)),
                "wo": lin(kg[4], (n, H * m.v_head_dim, d)),
            }
            if routed:
                g.update(init_moe_params(kg[5], d, moe, n_layers=n))
            else:
                ff = moe.d_dense_ff
                g.update(w_gate=lin(kg[6], (n, d, ff)),
                         w_up=lin(kg[7], (n, d, ff)),
                         w_down=lin(kg[8], (n, ff, d)))
            params[name] = g
        return params

    def _mlp(self, pl, x, routed):
        """-> (x + the layer's MLP, expert ids (T, k) | None).  A routed
        layer's ``pl`` holds its group's whole expert stacks and its
        index in the group (``_scan_layers``)."""
        h = C.rms_norm(x, pl["ln_ffn"], self.cfg.norm_eps)
        if not routed:
            return x + C.swiglu(h, pl["w_gate"], pl["w_up"],
                                pl["w_down"]), None
        y, ids = moe_ffn(h, {k: pl[k] for k in _MOE_KEYS if k in pl},
                         self.cfg.moe, layer=pl["index"])
        return x + y, ids

    def _scan_layers(self, step, x, group, routed, extra=(), unroll=1):
        """``lax.scan`` of ``step(x, (pl, *extra slices))`` over a layer
        group.  The routed experts do not go through the scan: each
        layer sees the group's (n*E, ...) stacks whole, with its index
        in the group, so no layer's experts are sliced out and copied."""
        n = jax.tree.leaves(group)[0].shape[0]
        stacks = {}
        if routed:
            group = dict(group)
            E = self.cfg.moe.n_experts
            for k in _EXPERT_KEYS:
                w = group.pop(k)
                stacks[k] = w.reshape(n * E, *w.shape[2:])
            group["index"] = jnp.arange(n, dtype=jnp.int32)

        def body(x, xs):
            pl, *rest = xs
            return step(x, (dict(pl, **stacks), *rest))
        return jax.lax.scan(body, x, (group, *extra),
                            unroll=max(1, min(int(unroll), n)))

    # -- rope, latents and queries -------------------------------------- #
    def _rope_cs(self, positions):
        """YaRN cos/sin of shape positions.shape + (rope/2,), fp32."""
        m = self.cfg.mla
        inv = jnp.asarray(yarn_inv_freq(m, self.cfg.rope_theta))
        ang = positions.astype(jnp.float32)[..., None] * inv
        sc = (yarn_get_mscale(m.factor, m.mscale)
              / yarn_get_mscale(m.factor, m.mscale_all_dim))
        return jnp.cos(ang) * sc, jnp.sin(ang) * sc

    def _latents(self, pl, h, positions):
        """h: (B,S,d) -> (ckv (B,S,rank), kpe (B,S,rope)) roped."""
        m = self.cfg.mla
        kv = h @ pl["w_dkv"]
        ckv, kpe = kv[..., :m.kv_lora_rank], kv[..., m.kv_lora_rank:]
        ckv = C.rms_norm(ckv, pl["ln_kv"], self.cfg.norm_eps)
        cos, sin = self._rope_cs(positions)
        kpe = C.apply_rope(kpe[..., None, :], cos, sin)[..., 0, :]
        return ckv, kpe

    def _queries(self, pl, h, positions):
        """-> (q_nope, q_pe), both times the YaRN softmax-scale factor
        (``softmax_scale * sqrt(qk_head_dim)``), so that attention keeps
        its plain ``1/sqrt(qk_head_dim)``."""
        m, cfg = self.cfg.mla, self.cfg
        B, S, _ = h.shape
        qk_hd = m.qk_nope_head_dim + m.qk_rope_head_dim
        q = (h @ pl["wq"]).reshape(B, S, cfg.n_heads, qk_hd)
        q_nope, q_pe = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
        cos, sin = self._rope_cs(positions)
        q_pe = C.apply_rope(q_pe, cos, sin)
        f = softmax_scale(m) * math.sqrt(qk_hd)
        if f != 1.0:
            q_nope, q_pe = ((t.astype(jnp.float32) * f).astype(t.dtype)
                            for t in (q_nope, q_pe))
        return q_nope, q_pe

    def _expand_kv(self, pl, ckv, kpe):
        """Latent -> per-head K (nope+rope) and V.  ckv (B,S,rank)."""
        m, H = self.cfg.mla, self.cfg.n_heads
        B, S, _ = ckv.shape
        k_nope = (ckv @ pl["w_uk"]).reshape(B, S, H, m.qk_nope_head_dim)
        v = (ckv @ pl["w_uv"]).reshape(B, S, H, m.v_head_dim)
        kpe_h = jnp.broadcast_to(kpe[:, :, None, :],
                                 (B, S, H, m.qk_rope_head_dim))
        k = jnp.concatenate([k_nope, kpe_h.astype(k_nope.dtype)], axis=-1)
        return k, v

    # -- full-sequence layers ------------------------------------------- #
    def _layer_full(self, pl, x, positions, window, n_sinks, want_density,
                    return_kv, routed=True):
        h = C.rms_norm(x, pl["ln_attn"], self.cfg.norm_eps)
        q_nope, q_pe = self._queries(pl, h, positions)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
        ckv, kpe = self._latents(pl, h, positions)
        k, v = self._expand_kv(pl, ckv, kpe)
        S = x.shape[1]
        if (S > 2048 or window) and not want_density:
            out = C.flash_attention(q, k, v, 0, 1024, window, n_sinks)
            ao = C.AttnOut(out, None)
        elif S > 2048 or window:
            ao = C.blocked_causal_attention(q, k, v, block=1024, window=window,
                                            n_sinks=n_sinks,
                                            want_density=want_density)
        else:
            mask = C.causal_window_mask(positions, positions, window, n_sinks)
            ao = C.gqa_attention(q, k, v, mask, want_density=want_density)
        x = x + ao.out.reshape(*x.shape[:2], -1) @ pl["wo"]
        x, _ = self._mlp(pl, x, routed)
        extras = {}
        if want_density:
            extras["density"] = ao.key_density
        if return_kv:
            extras["ckv"], extras["kpe"] = ckv, kpe
        return x, extras

    def _stack_full(self, params, tokens, *, window=0, n_sinks=0,
                    want_density=False, return_kv=False, remat=False):
        cfg = self.cfg
        x = C.constrain_batch(params["embed"][tokens].astype(jnp.bfloat16))
        S = tokens.shape[1]
        positions = jax.lax.broadcasted_iota(jnp.int32, (S,), 0)
        outs = []
        for name, _, _, routed in self._groups():
            def body(x, pl, routed=routed):
                x, extras = self._layer_full(pl, x, positions, window,
                                             n_sinks, want_density,
                                             return_kv, routed)
                return C.constrain_batch(x), extras
            if remat:
                body = jax.checkpoint(body)
            x, extras = self._scan_layers(
                lambda x, xs, body=body: body(x, xs[0]), x, params[name],
                routed)
            outs.append(extras)
        x = C.rms_norm(x, params["ln_f"], cfg.norm_eps)
        return x, _concat_groups(outs)

    def prefill(self, params, batch, want_density=False, window=0, n_sinks=0):
        tokens = batch["tokens"]
        x, extras = self._stack_full(
            params, tokens, window=window, n_sinks=n_sinks,
            want_density=want_density, return_kv=True)
        logits = (x[:, -1] @ self.head_weight(params)).astype(jnp.float32)
        cache = {"ckv": extras["ckv"], "kpe": extras["kpe"],
                 "pos": jnp.int32(tokens.shape[1])}
        density = None
        if want_density:
            density = jnp.mean(extras["density"], axis=0)
        return PrefillOut(logits, cache, density)

    def _scan_groups(self, params, body, x, cache, leaves, unroll=1):
        """Run ``body(x, (pl, *leaf slices), routed)`` over each layer
        group, with each cache leaf cut to the group's layers; -> (x,
        the groups' ``ys`` joined along the layer axis)."""
        outs = []
        for name, lo, hi, routed in self._groups():
            def step(x, layer_in, routed=routed):
                return body(x, layer_in, routed)
            x, ys = self._scan_layers(
                step, x, params[name], routed,
                tuple(cache[n][lo:hi] for n in leaves), unroll)
            outs.append(ys)
        return x, _concat_groups(outs)

    # -- absorbed decode ------------------------------------------------- #
    def decode_step(self, params, tokens, cache, window=0, n_sinks=0,
                    want_density=False, unroll: int = 1):
        """Scalar ``pos``: every row decodes at one position; (B,) pos:
        each row at its own (batched / paged decode)."""
        cfg, m = self.cfg, self.cfg.mla
        H = cfg.n_heads
        x = C.constrain_batch(
            params["embed"][tokens].astype(jnp.bfloat16))      # (B,1,d)
        pos = cache["pos"]
        positions = pos[None] if pos.ndim == 0 else pos[:, None]
        pos_col = pos if pos.ndim == 0 else pos[:, None]       # vs (1|B, S)
        qk_scale = 1.0 / jnp.sqrt(jnp.float32(m.qk_nope_head_dim
                                              + m.qk_rope_head_dim))

        mixed = "ckv_q" in cache         # bf16 window + int8 latent tier
        if mixed:
            # the new token lands in the bf16 window: clear its
            # quant-mask bit once (the mask is shared across layers)
            S = cache["ckv"].shape[2]
            s_pos = jax.lax.broadcasted_iota(jnp.int32, (S,), 0)
            idx = pos[None] if pos.ndim == 0 else pos
            qm = cache["quant_mask"] & ~(s_pos[None, :] == idx[:, None])[None]

        def body(x, layer_in, routed):
            ckvq_c = kpeq_c = ckvs_c = kpes_c = None
            if mixed:
                pl, ckv_c, kpe_c, ckvq_c, kpeq_c, ckvs_c, kpes_c = layer_in
            else:
                pl, ckv_c, kpe_c = layer_in
            h = C.rms_norm(x, pl["ln_attn"], cfg.norm_eps)
            q_nope, q_pe = self._queries(pl, h, positions)      # (B,1,H,*)
            ckv_t, kpe_t = self._latents(pl, h, positions)
            ckv_t = ckv_t.astype(ckv_c.dtype)                   # (B,1,rank)
            kpe_t = kpe_t.astype(kpe_c.dtype)
            if mixed:
                ckv_att = _latent_select(ckv_c, ckvq_c, ckvs_c, qm[0])
                kpe_att = _latent_select(kpe_c, kpeq_c, kpes_c, qm[0])
            else:
                ckv_att, kpe_att = ckv_c, kpe_c
            # absorb W_uk into q:  q_abs (B,1,H,rank)
            w_uk = pl["w_uk"].reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
            q_abs = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)

            def scores(ckv, kpe):
                return (jnp.einsum("bqhr,bsr->bhqs", q_abs, ckv,
                                   preferred_element_type=jnp.float32)
                        + jnp.einsum("bqhr,bsr->bhqs", q_pe, kpe,
                                     preferred_element_type=jnp.float32)
                        ) * qk_scale
            # the cached positions before ``pos``, read where they lie,
            # and the round's own token joined in the same softmax: no
            # layer writes a copy of the cache
            S = ckv_c.shape[1]
            k_pos = jax.lax.broadcasted_iota(jnp.int32, (S,), 0)[None, :]
            valid = k_pos < pos_col
            if window:
                valid = valid & ((k_pos >= pos_col + 1 - window)
                                 | (k_pos < n_sinks))
            s = jnp.where(valid[:, None, None, :], scores(ckv_att, kpe_att),
                          C.NEG_INF)
            p = jax.nn.softmax(
                jnp.concatenate([s, scores(ckv_t, kpe_t)], axis=-1), axis=-1)
            p_new = p[..., S:]                                  # (B,H,1,1)
            ctx = jnp.einsum("bhqs,bsr->bqhr", p[..., :S].astype(
                ckv_att.dtype), ckv_att)
            ctx = (ctx.astype(jnp.float32) + jnp.einsum(
                "bhqs,bsr->bqhr", p_new, ckv_t.astype(jnp.float32))
                   ).astype(ckv_att.dtype)
            w_uv = pl["w_uv"].reshape(m.kv_lora_rank, H, m.v_head_dim)
            out = jnp.einsum("bqhr,rhv->bqhv", ctx, w_uv)
            x = x + out.reshape(*x.shape[:2], -1) @ pl["wo"]
            x, ids = self._mlp(pl, x, routed)
            ys = {"ckv": ckv_t[:, 0], "kpe": kpe_t[:, 0]}       # (B, ...)
            if ids is not None:
                ys["experts"] = ids                             # (B, k)
            if want_density:
                # Eq.-1 key mass at the decoded position: head-mean of
                # the softmax row over the latent sequence, the new
                # token's at ``pos``
                ys["mass"] = (jnp.mean(p[:, :, 0, :S], axis=1)
                              + jnp.where(k_pos == pos_col,
                                          jnp.mean(p_new[:, :, 0], axis=1),
                                          0.0))                # (B, S)
            return C.constrain_batch(x), ys

        leaves = ("ckv", "kpe") + (_LATENT_QUANT_LEAVES if mixed else ())
        x, ys = self._scan_groups(params, body, x, cache, leaves, unroll)
        x = C.rms_norm(x, params["ln_f"], cfg.norm_eps)
        logits = (x[:, 0] @ self.head_weight(params)).astype(jnp.float32)
        new_cache = {n: _write_token(cache[n], ys[n], pos)
                     for n in ("ckv", "kpe")}
        new_cache["pos"] = pos + 1
        if mixed:
            for n in _LATENT_QUANT_LEAVES:
                new_cache[n] = cache[n]
            new_cache["quant_mask"] = qm
        out = DecodeOut(logits, new_cache, ys.get("experts"),
                        {n: ys[n] for n in ("ckv", "kpe")})
        if want_density:
            return out, jnp.mean(ys["mass"], axis=0)            # (B, S)
        return out

    def _build_cache(self, batch, seq, dtype, layout):
        cfg, m = self.cfg, self.cfg.mla
        L = cfg.n_layers
        cache = {
            "ckv": jnp.zeros((L, batch, seq, m.kv_lora_rank), dtype),
            "kpe": jnp.zeros((L, batch, seq, m.qk_rope_head_dim), dtype),
            "pos": jnp.int32(0),
        }
        if layout == LAYOUT_MIXED:
            # mixed-precision working cache: bf16 latent window + int8
            # quant-resident segments with per-(token, layer) scales
            # over the whole rank vector, selected by quant_mask (dummy
            # leading axis: axis 1 stays the batch axis on every leaf)
            cache["ckv_q"] = jnp.zeros((L, batch, seq, m.kv_lora_rank),
                                       jnp.int8)
            cache["kpe_q"] = jnp.zeros((L, batch, seq, m.qk_rope_head_dim),
                                       jnp.int8)
            cache["ckv_scale"] = jnp.zeros((L, batch, seq), jnp.float32)
            cache["kpe_scale"] = jnp.zeros((L, batch, seq), jnp.float32)
            cache["quant_mask"] = jnp.zeros((1, batch, seq), bool)
        return cache

    # -- Fig. 7 recompute over latent chunks ----------------------------- #
    def recompute(self, params, miss_tokens, miss_pos, cache, seq_len,
                  window: int = 0, n_sinks: int = 0, want_density=False):
        cfg = self.cfg
        x = C.constrain_batch(
            params["embed"][miss_tokens].astype(jnp.bfloat16))
        S = cache["ckv"].shape[2]
        k_pos_all = jax.lax.broadcasted_iota(jnp.int32, (S,), 0)
        mixed = "ckv_q" in cache
        if mixed:
            # recomputed positions land in the bf16 window; resident
            # quant latents are read THROUGH during attention
            qm = cache["quant_mask"] & ~jnp.any(
                k_pos_all[None, :] == miss_pos[:, None], axis=0)[None, None]

        def body(x, layer_in, routed):
            ckvq_c = kpeq_c = ckvs_c = kpes_c = None
            if mixed:
                pl, ckv_c, kpe_c, ckvq_c, kpeq_c, ckvs_c, kpes_c = layer_in
            else:
                pl, ckv_c, kpe_c = layer_in
            h = C.rms_norm(x, pl["ln_attn"], cfg.norm_eps)
            q_nope, q_pe = self._queries(pl, h, miss_pos)
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
            ckv_t, kpe_t = self._latents(pl, h, miss_pos)
            ckv_c = ckv_c.at[:, miss_pos].set(ckv_t.astype(ckv_c.dtype))
            kpe_c = kpe_c.at[:, miss_pos].set(kpe_t.astype(kpe_c.dtype))
            if mixed:
                ckv_att = _latent_select(ckv_c, ckvq_c, ckvs_c, qm[0])
                kpe_att = _latent_select(kpe_c, kpeq_c, kpes_c, qm[0])
            else:
                ckv_att, kpe_att = ckv_c, kpe_c
            k, v = self._expand_kv(pl, ckv_att.astype(x.dtype),
                                   kpe_att.astype(x.dtype))
            mask = C.causal_window_mask(miss_pos, k_pos_all, window, n_sinks)
            mask = mask & (k_pos_all < seq_len)[None, :]
            ao = C.gqa_attention(q, k, v, mask, want_density=want_density)
            x = x + ao.out.reshape(*x.shape[:2], -1) @ pl["wo"]
            x, _ = self._mlp(pl, x, routed)
            ys = {"ckv": ckv_c, "kpe": kpe_c}
            if want_density:
                ys["density"] = ao.key_density
            return C.constrain_batch(x), ys

        leaves = ("ckv", "kpe") + (_LATENT_QUANT_LEAVES if mixed else ())
        x, ys = self._scan_groups(params, body, x, cache, leaves)
        x = C.rms_norm(x, params["ln_f"], cfg.norm_eps)
        density = jnp.mean(ys["density"], axis=0) if want_density else None
        new_cache = {"ckv": ys["ckv"], "kpe": ys["kpe"], "pos": cache["pos"]}
        if mixed:
            for n in _LATENT_QUANT_LEAVES:
                new_cache[n] = cache[n]
            new_cache["quant_mask"] = qm
        return new_cache, x, density
