"""Plain reference for the DeepSeek-V2 configurations (multi-head latent
attention with YaRN rope, a dense first layer, then routed experts with
shared experts), as ``deepseek_v2``'s published modeling code computes
them.

Everything a cell of such a configuration needs from it:

- ``sizes``: the shapes, read from the configuration's published keys.
  ``KV`` 1 and ``hd`` (kv_lora_rank + qk_rope_head_dim) / 2 make the
  harness's ``2 * L * KV * hd * 2`` count the latent's true bytes a
  token: 576 bf16 values a layer, 1,152 bytes;
- ``make_params``: the served weights, made on the device from the seed
  in one jitted call, in bf16 (norm scales in float32), in the
  parameter layout the serving program takes: ``dense`` (the leading
  dense layers) and ``layers`` (the routed ones), each stacked on a
  leading layer axis;
- ``program_config``: the keyword arguments of the program's model
  configuration (the only import of the serving program: its config
  dataclasses);
- ``Reference``: a straightforward float32 forward pass at
  ``precision=highest``, layer by layer and expert by expert, with the
  weights computed at a lower precision for the control (``int8``:
  symmetric per output channel; ``fp8``: e4m3 with a per output channel
  scale).

The reference follows the published computation: queries and the
latent from full-rank projections, the latent RMS-normed, per-head keys
and values expanded from the stored latent (the naive form, not the
absorbed one the program decodes with), YaRN inverse frequencies on the
64 roped dimensions and the softmax scale ``qk_head_dim^-0.5 *
mscale(factor, mscale_all_dim)^2``; layer 0 a dense SwiGLU MLP; every
other layer evaluates each of its routed experts on every token and
weighs it by the token's top-k softmax router score (zero outside the
top k; greedy, neither renormalised nor scaled), plus the shared
experts.  Departures, each a relabelling or a cut that the
program shares:

- rope pairs are (x[i], x[i + d/2]), rotate-half.  The published code
  de-interleaves its (x[2i], x[2i+1]) pairs into this order before it
  rotates, so this is the published computation with the rope columns
  of ``wq`` and ``w_dkv`` permuted, and the weights are random;
- the router's ties are broken by the lower expert id (``lax.top_k``);
- the depth is the configuration's ``num_hidden_layers`` (the chip's
  pipeline stage).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def sizes(cfg: dict) -> dict:
    assert cfg.get("topk_method", "greedy") == "greedy"
    assert cfg.get("n_group", 1) == 1 and cfg.get("q_lora_rank") is None
    assert cfg.get("scoring_func", "softmax") == "softmax"
    assert not cfg["norm_topk_prob"] and cfg["routed_scaling_factor"] == 1
    assert not cfg["tie_word_embeddings"]
    r, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    ys = cfg.get("rope_scaling") or {}
    return dict(L=cfg["num_hidden_layers"], d=cfg["hidden_size"],
                H=cfg["num_attention_heads"], KV=1, hd=(r + rope) // 2,
                r=r, rope=rope, nope=cfg["qk_nope_head_dim"],
                vd=cfg["v_head_dim"], ff=cfg["intermediate_size"],
                fe=cfg["moe_intermediate_size"],
                E=cfg["n_routed_experts"], k=cfg["num_experts_per_tok"],
                fs=cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
                nd=cfg["first_k_dense_replace"], V=cfg["vocab_size"],
                theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]),
                factor=float(ys.get("factor", 1.0)),
                orig=int(ys.get("original_max_position_embeddings", 4096)),
                beta_fast=float(ys.get("beta_fast", 32)),
                beta_slow=float(ys.get("beta_slow", 1)),
                mscale=float(ys.get("mscale", 1.0)),
                mscale_all_dim=float(ys.get("mscale_all_dim", 0.0)))


def program_config(cfg: dict) -> dict:
    from repro.configs.base import MLAConfig, MoEConfig
    s = sizes(cfg)
    mla = MLAConfig(kv_lora_rank=s["r"], q_lora_rank=0,
                    qk_nope_head_dim=s["nope"], qk_rope_head_dim=s["rope"],
                    v_head_dim=s["vd"], factor=s["factor"],
                    original_max_position_embeddings=s["orig"],
                    beta_fast=s["beta_fast"], beta_slow=s["beta_slow"],
                    mscale=s["mscale"], mscale_all_dim=s["mscale_all_dim"])
    moe = MoEConfig(n_experts=s["E"], top_k=s["k"], d_expert=s["fe"],
                    n_shared=cfg["n_shared_experts"], d_shared=s["fs"],
                    first_dense=s["nd"], d_dense_ff=s["ff"])
    return dict(name=cfg["name"], family="mla_moe", n_layers=s["L"],
                d_model=s["d"], n_heads=s["H"], n_kv_heads=s["H"],
                d_ff=s["ff"], vocab=s["V"], head_dim=s["vd"],
                rope_theta=s["theta"], tie_embeddings=False,
                norm_eps=s["eps"],
                max_seq=int(cfg["max_position_embeddings"]),
                mla=mla, moe=moe)


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (64 bits kept)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make_params(cfg: dict, seed: int):
    """The served weights, on the default device, from ``seed``."""
    s = sizes(cfg)
    return _make(tuple(sorted(s.items())))(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _make(items):
    s = dict(items)
    d, H, r, rope, nope, vd = (s[k] for k in ("d", "H", "r", "rope", "nope",
                                               "vd"))
    E, fe, fs = s["E"], s["fe"], s["fs"]

    def gen(key):
        names = iter(range(1 << 16))

        def lin(n, shape, fan_in, std=0.6):
            """(n, *shape) normal(0, std / sqrt(fan_in)), drawn in bf16
            one layer at a time, so that no float32 copy and no random
            bits of more than a layer are held."""
            k = jax.random.fold_in(key, next(names))
            sd = jnp.asarray(std / math.sqrt(fan_in), jnp.bfloat16)
            return jax.lax.map(
                lambda kl: jax.random.normal(kl, shape, jnp.bfloat16) * sd,
                jax.random.split(k, n))

        def scale(shape):
            k = jax.random.fold_in(key, next(names))
            return 1.0 + 0.05 * jax.random.normal(k, shape, F32)

        def group(n, routed):
            g = {"ln_attn": scale((n, d)), "ln_ffn": scale((n, d)),
                 "ln_kv": scale((n, r)),
                 "wq": lin(n, (d, H * (nope + rope)), d),
                 "w_dkv": lin(n, (d, r + rope), d),
                 "w_uk": lin(n, (r, H * nope), r),
                 "w_uv": lin(n, (r, H * vd), r),
                 "wo": lin(n, (H * vd, d), H * vd)}
            if not routed:
                g.update(w_gate=lin(n, (d, s["ff"]), d),
                         w_up=lin(n, (d, s["ff"]), d),
                         w_down=lin(n, (s["ff"], d), s["ff"]))
                return g
            # a router about as decisive as a trained one: logits of
            # standard deviation ~2, so the top k take ~0.4 of the mass
            g.update(router=lin(n, (d, E), d, std=2.0),
                     w_gate=lin(n, (E, d, fe), d),
                     w_up=lin(n, (E, d, fe), d),
                     w_down=lin(n, (E, fe, d), fe),
                     s_gate=lin(n, (d, fs), d), s_up=lin(n, (d, fs), d),
                     s_down=lin(n, (fs, d), fs))
            return g

        params = {"embed": lin(1, (s["V"], d), d)[0],
                  "head": lin(1, (d, s["V"]), d)[0],
                  "ln_f": scale((d,))}
        if s["nd"]:
            params["dense"] = group(s["nd"], False)
        params["layers"] = group(s["L"] - s["nd"], True)
        return params

    return jax.jit(gen)


# --------------------------------------------------------------------- #
# lower-precision weights for the control
# --------------------------------------------------------------------- #
def _fake_quant(w, mode: str, axis: int):
    """Quantize-dequantize ``w`` (float32) per slice along ``axis`` (the
    reduction axis of its matmul: one scale per output channel)."""
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    if mode == "int8":
        sc = jnp.maximum(amax / 127.0, 1e-12)
        return jnp.clip(jnp.round(w / sc), -127, 127) * sc
    if mode == "fp8":
        sc = jnp.maximum(amax / 448.0, 1e-12)
        return (w / sc).astype(jnp.float8_e4m3fn).astype(F32) * sc
    raise ValueError(mode)


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn(s: dict):
    """(inverse frequencies (rope/2,), cos/sin factor, softmax scale) as
    DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding`` and attention set
    them."""
    dim, base, f = s["rope"], s["theta"], s["factor"]
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    scale = (s["nope"] + s["rope"]) ** -0.5
    if f <= 1:
        return extra, 1.0, scale

    def corr_dim(rot):
        return (dim * math.log(s["orig"] / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(s["beta_fast"])), 0)
    high = min(math.ceil(corr_dim(s["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv = extra / f * (1 - mask) + extra * mask
    if s["mscale_all_dim"]:
        scale *= _mscale(f, s["mscale_all_dim"]) ** 2
    return (inv, _mscale(f, s["mscale"]) / _mscale(f, s["mscale_all_dim"]),
            scale)


def _rope(x, pos, inv, cs):
    """x (S, n, rope) float32, rotate-half over (x[i], x[i + rope/2])."""
    half = x.shape[-1] // 2
    ang = pos.astype(F32)[:, None] * jnp.asarray(inv, F32)
    c, s = (jnp.cos(ang) * cs)[:, None, :], (jnp.sin(ang) * cs)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


class Reference:
    """A context replayed segment by segment in float32 at
    ``precision=highest``, with its own latent store (``ckv``, ``kpe``
    per layer and position), so that it holds what the service holds:

    - ``extend`` appends a segment at positions [n0, n0 + len): its
      queries attend the store and themselves, causally; rows from
      ``n_fed`` on are stored as zero (a call's last token is emitted
      but never fed); it returns the logits after chosen positions,
      and keeps in ``routes`` the experts each of them chose in each
      routed layer, (L_moe, len(at), k).  Segments run at the next power of two of their length (at least
      256), so that a short one costs less;
    - ``quantize`` applies the chunk codec's arithmetic to one chunk of
      the store (symmetric, one scale per layer and channel over the
      chunk's tokens, ``2**(bits-1) - 1`` levels each side).

    ``weights`` selects the precision of every weight matrix: ``bf16``
    (the served weights exactly, computed in float32) or a lower one for
    the control."""

    Q_BLOCK = 256
    MIN_BUCKET = 256

    def __init__(self, cfg: dict, n_slots: int, chunk_tokens: int,
                 n_logits: int):
        self.s = sizes(cfg)
        self.M = n_slots // 2              # the longest segment
        self.S = n_slots + self.M          # store rows, room for padding
        self.cs = chunk_tokens
        self.A = n_logits
        self.inv, self.cs_scale, self.scale = yarn(self.s)
        self._ext, self._q = {}, {}

    def new_store(self):
        s = self.s
        return (jnp.zeros((s["L"], self.S, s["r"]), F32),
                jnp.zeros((s["L"], self.S, s["rope"]), F32))

    def bucket(self, n: int) -> int:
        b = min(self.MIN_BUCKET, self.M)
        while b < n:
            b *= 2
        return min(b, self.M)

    def extend(self, params, store, tokens, n0: int, n_fed: int,
               at=(), weights: str = "bf16"):
        n = len(tokens)
        assert n <= self.M and len(at) <= self.A, (n, len(at))
        Mb = self.bucket(n)
        tok = np.zeros(Mb, np.int32)
        tok[:n] = tokens
        pos = np.zeros(self.A, np.int32)
        pos[:len(at)] = at
        key = (weights, Mb, bool(len(at)))
        fn = self._ext.get(key)
        if fn is None:
            fn = jax.jit(functools.partial(self._extend, mode=weights,
                                           logits=bool(len(at))))
            self._ext[key] = fn
        c, p, lg, routes = fn(params, store[0], store[1],
                              jnp.asarray(tok), jnp.int32(n0),
                              jnp.int32(n_fed), jnp.asarray(pos))
        if not len(at):
            return (c, p), None
        self.routes = np.asarray(routes)[:, :len(at)]
        return (c, p), np.asarray(lg)[:len(at)]

    def quantize(self, store, chunk: int, bits: int):
        if bits >= 16:
            return store
        fn = self._q.get(bits)
        if fn is None:
            fn = jax.jit(functools.partial(self._quantize, bits=bits))
            self._q[bits] = fn
        return fn(store[0], store[1], jnp.int32(chunk * self.cs))

    def _quantize(self, c, p, lo, bits):
        qm = (1 << (bits - 1)) - 1
        out = []
        for a in (c, p):
            blk = jax.lax.dynamic_slice_in_dim(a, lo, self.cs, axis=1)
            sc = jnp.maximum(jnp.max(jnp.abs(blk), axis=1, keepdims=True)
                             / qm, 1e-8)
            blk = jnp.clip(jnp.round(blk / sc), -qm, qm) * sc
            out.append(jax.lax.dynamic_update_slice_in_dim(a, blk, lo,
                                                           axis=1))
        return tuple(out)

    def _w(self, w, mode, axis):
        w = w.astype(F32)
        return w if mode == "bf16" else _fake_quant(w, mode, axis)

    def _extend(self, params, c_store, p_store, tok, n0, n_fed, at, mode,
                logits):
        s, S = self.s, self.S
        M = tok.shape[0]
        H, r, rope, nope, vd, eps = (s[k] for k in ("H", "r", "rope", "nope",
                                                    "vd", "eps"))
        rows = jnp.arange(M, dtype=jnp.int32)
        pos = n0 + rows
        kpos = jnp.arange(S, dtype=jnp.int32)
        fed = (rows < n_fed)[:, None]
        x = self._w(params["embed"][tok], mode, 1)
        qb = min(self.Q_BLOCK, M)
        nb = M // qb

        def mm(a, w):
            return jnp.dot(a, w, precision=HIGHEST)

        def w_(pl, name):
            return self._w(pl[name], mode, 0)

        def swiglu(h, wg, wu, wd):
            return mm(jax.nn.silu(mm(h, wg)) * mm(h, wu), wd)

        def attention(x, pl, cs_, ps_):
            h = _rms(x, pl["ln_attn"], eps)
            q = mm(h, w_(pl, "wq")).reshape(M, H, nope + rope)
            q = jnp.concatenate(
                [q[..., :nope], _rope(q[..., nope:], pos, self.inv,
                                      self.cs_scale)], -1)
            kv = mm(h, w_(pl, "w_dkv"))
            ckv = _rms(kv[:, :r], pl["ln_kv"], eps)
            kpe = _rope(kv[:, None, r:], pos, self.inv, self.cs_scale)[:, 0]
            # an unfed row is the segment's last: no query of it is read
            cs_ = jax.lax.dynamic_update_slice_in_dim(
                cs_, jnp.where(fed, ckv, 0.0), n0, axis=0)
            ps_ = jax.lax.dynamic_update_slice_in_dim(
                ps_, jnp.where(fed, kpe, 0.0), n0, axis=0)
            # the naive form: per-head keys and values from the latent
            k = jnp.concatenate(
                [mm(cs_, w_(pl, "w_uk")).reshape(S, H, nope),
                 jnp.broadcast_to(ps_[:, None, :], (S, H, rope))], -1)
            v = mm(cs_, w_(pl, "w_uv")).reshape(S, H, vd)

            def block(args):
                qi, b = args
                sc = jnp.einsum("qhd,khd->hqk", qi, k,
                                precision=HIGHEST) * self.scale
                qp = n0 + b * qb + jnp.arange(qb)
                sc = jnp.where((kpos[None, :] <= qp[:, None])[None], sc,
                               -1e30)
                p = jax.nn.softmax(sc, axis=-1)
                return jnp.einsum("hqk,khd->qhd", p, v, precision=HIGHEST)

            o = jax.lax.map(block, (q.reshape(nb, qb, H, nope + rope),
                                    jnp.arange(nb))).reshape(M, H * vd)
            return x + mm(o, w_(pl, "wo")), cs_, ps_

        def dense_layer(x, xs):
            pl, cs_, ps_ = xs
            x, cs_, ps_ = attention(x, pl, cs_, ps_)
            h = _rms(x, pl["ln_ffn"], eps)
            x = x + swiglu(h, w_(pl, "w_gate"), w_(pl, "w_up"),
                           w_(pl, "w_down"))
            return x, (cs_, ps_)

        def moe_layer(x, xs):
            pl, cs_, ps_ = xs
            x, cs_, ps_ = attention(x, pl, cs_, ps_)
            h = _rms(x, pl["ln_ffn"], eps)
            score = jax.nn.softmax(mm(h, w_(pl, "router")), axis=-1)
            w, idx = jax.lax.top_k(score, s["k"])
            gate = jnp.zeros_like(score).at[rows[:, None], idx].set(
                w)                                           # (M, E)

            def expert(y, e):
                out = swiglu(h, self._w(pl["w_gate"][e], mode, 0),
                             self._w(pl["w_up"][e], mode, 0),
                             self._w(pl["w_down"][e], mode, 0))
                return y + gate[:, e, None] * out, None

            y, _ = jax.lax.scan(expert, jnp.zeros_like(h),
                                jnp.arange(s["E"]))
            y = y + swiglu(h, w_(pl, "s_gate"), w_(pl, "s_up"),
                           w_(pl, "s_down"))
            return x + y, (cs_, ps_, idx[at])

        nd = s["nd"]
        outs = []
        if nd:
            x, kv = jax.lax.scan(dense_layer, x, (params["dense"],
                                                  c_store[:nd],
                                                  p_store[:nd]))
            outs.append(kv)
        x, kv = jax.lax.scan(moe_layer, x, (params["layers"], c_store[nd:],
                                            p_store[nd:]))
        outs.append(kv)
        c_store = jnp.concatenate([o[0] for o in outs], 0)
        p_store = jnp.concatenate([o[1] for o in outs], 0)
        if not logits:
            return c_store, p_store, None, None
        x = _rms(x[at], params["ln_f"], eps)
        # the head in vocabulary blocks, so that no float32 copy of the
        # whole head is ever held
        V = s["V"]
        nv = next(n for n in range(max(1, V * s["d"] // (1 << 27)), V + 1)
                  if V % n == 0)
        blocks = jnp.moveaxis(params["head"].reshape(s["d"], nv, V // nv),
                              1, 0)
        out = jax.lax.map(lambda w: mm(x, self._w(w, mode, 0)), blocks)
        return (c_store, p_store,
                jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V), kv[2])
