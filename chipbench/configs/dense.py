"""Plain reference for the dense decoder configurations (Llama/Qwen2
layout: RMSNorm, RoPE rotate-half, grouped-query attention with optional
QKV bias, SwiGLU MLP, tied or untied LM head).

Everything a dense cell needs from its configuration lives here:

- ``make_params``: the served weights, made on the device from the seed
  in one jitted call, in bf16 (norm scales and biases in float32), in
  the parameter layout the serving program takes.  Matrices are
  normal with standard deviation 0.6 / sqrt(fan-in) (0.019 at
  smollm's width), so attention and the MLP see the same spread of
  inputs at every width and the output depends on the context;
- ``program_config``: the keyword arguments of the program's model
  configuration;
- ``Reference``: a straightforward float32 forward pass at
  ``precision=highest``, with the weights computed at a lower precision
  for the control (``int8``: symmetric per output channel; ``fp8``:
  e4m3 with a per output channel scale).

It imports nothing of the serving program.
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
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(L=cfg["num_hidden_layers"], d=d, H=H,
                KV=cfg["num_key_value_heads"],
                hd=cfg.get("head_dim") or d // H,
                ff=cfg["intermediate_size"], V=cfg["vocab_size"],
                tied=bool(cfg["tie_word_embeddings"]),
                bias=bool(cfg.get("attention_bias", False)),
                theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]))


def program_config(cfg: dict) -> dict:
    s = sizes(cfg)
    return dict(name=cfg["name"], family="dense", n_layers=s["L"],
                d_model=s["d"], n_heads=s["H"], n_kv_heads=s["KV"],
                d_ff=s["ff"], vocab=s["V"], head_dim=s["hd"],
                qkv_bias=s["bias"], rope_theta=s["theta"],
                tie_embeddings=s["tied"], norm_eps=s["eps"],
                max_seq=int(cfg["max_position_embeddings"]))


def seed_key(seed: int):
    """A PRNG key from any non-negative whole number (64 bits kept)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _param_shapes(s: dict) -> dict:
    L, d, H, KV, hd, ff, V = (s[k] for k in ("L", "d", "H", "KV", "hd",
                                              "ff", "V"))
    layers = {"wq": (L, d, H * hd), "wk": (L, d, KV * hd),
              "wv": (L, d, KV * hd), "wo": (L, H * hd, d),
              "w_gate": (L, d, ff), "w_up": (L, d, ff),
              "w_down": (L, ff, d)}
    shapes = {"embed": (V, d), "layers": layers}
    if not s["tied"]:
        shapes["head"] = (d, V)
    return shapes


def make_params(cfg: dict, seed: int):
    """The served weights, on the default device, from ``seed``."""
    s = sizes(cfg)
    return _make(tuple(sorted(s.items())))(seed_key(seed))


@functools.lru_cache(maxsize=None)
def _make(items):
    s = dict(items)
    shapes = _param_shapes(s)
    L, d, H, KV, hd = s["L"], s["d"], s["H"], s["KV"], s["hd"]

    def gen(key):
        ks = iter(jax.random.split(key, 32))

        def lin(shape, fan_in):
            # the same spread of pre-activations at every width; drawn
            # in bf16 so that no float32 copy of a matrix is ever held
            bf = jnp.bfloat16
            return (jax.random.normal(next(ks), shape, bf)
                    * jnp.asarray(0.6 / math.sqrt(fan_in), bf))

        def scale(shape):
            return 1.0 + 0.05 * jax.random.normal(next(ks), shape, F32)

        layers = {n: lin(shp, shp[1]) for n, shp in shapes["layers"].items()}
        layers["ln_attn"] = scale((L, d))
        layers["ln_ffn"] = scale((L, d))
        if s["bias"]:
            for n, w in (("bq", H * hd), ("bk", KV * hd), ("bv", KV * hd)):
                layers[n] = 0.02 * jax.random.normal(next(ks), (L, w), F32)
        # the embedding doubles as the tied head, whose fan-in is d
        params = {"embed": lin(shapes["embed"], d), "ln_f": scale((d,)),
                  "layers": layers}
        if "head" in shapes:
            params["head"] = lin(shapes["head"], d)
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


def _rope(x, pos, theta):
    """x (S, n, hd) float32, rotate-half convention."""
    hd = x.shape[-1]
    half = hd // 2
    freqs = jnp.exp(jnp.arange(half, dtype=F32) * (-math.log(theta) / half))
    ang = pos.astype(F32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


class Reference:
    """A context replayed segment by segment in float32 at
    ``precision=highest``, with its own key/value store, so that it
    holds what the service holds:

    - ``extend`` appends a segment (a prompt, or a prompt and the tokens
      served for it) at positions [n0, n0 + len): its queries attend the
      store and themselves, causally; rows from ``n_fed`` on are stored
      as zero (a call's last token is emitted but never fed); it returns
      the logits after chosen positions of the segment;
    - ``quantize`` applies the chunk codec's arithmetic to one chunk of
      the store (symmetric, one scale per layer, head and channel over
      the chunk's tokens, ``2**(bits-1) - 1`` levels each side), as the
      service does when it re-encodes a chunk at a swap-out.

    ``weights`` selects the precision of every weight matrix: ``bf16``
    (the served weights exactly, computed in float32) or a lower one for
    the control."""

    Q_BLOCK = 512

    def __init__(self, cfg: dict, n_slots: int, chunk_tokens: int,
                 n_logits: int):
        self.s = sizes(cfg)
        self.M = n_slots // 2              # the longest segment
        self.S = n_slots + self.M          # store rows, room for padding
        self.cs = chunk_tokens
        self.A = n_logits
        self._ext, self._q = {}, {}

    def new_store(self):
        s = self.s
        z = jnp.zeros((s["L"], self.S, s["KV"], s["hd"]), F32)
        return (z, z)

    def extend(self, params, store, tokens, n0: int, n_fed: int,
               at=(), weights: str = "bf16"):
        n = len(tokens)
        assert n <= self.M and len(at) <= self.A, (n, len(at))
        tok = np.zeros(self.M, np.int32)
        tok[:n] = tokens
        pos = np.zeros(self.A, np.int32)
        pos[:len(at)] = at
        fn = self._ext.get(weights)
        if fn is None:
            fn = jax.jit(functools.partial(self._extend, mode=weights))
            self._ext[weights] = fn
        k, v, lg = fn(params, store[0], store[1], jnp.asarray(tok),
                      jnp.int32(n0), jnp.int32(n_fed), jnp.asarray(pos))
        return (k, v), (np.asarray(lg)[:len(at)] if len(at) else None)

    def quantize(self, store, chunk: int, bits: int):
        if bits >= 16:
            return store
        fn = self._q.get(bits)
        if fn is None:
            fn = jax.jit(functools.partial(self._quantize, bits=bits))
            self._q[bits] = fn
        return fn(store[0], store[1], jnp.int32(chunk * self.cs))

    def _quantize(self, k, v, lo, bits):
        qm = (1 << (bits - 1)) - 1
        out = []
        for a in (k, v):
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

    def _extend(self, params, k_store, v_store, tok, n0, n_fed, at, mode):
        s, M, S = self.s, self.M, self.S
        H, KV, hd, eps = s["H"], s["KV"], s["hd"], s["eps"]
        G = H // KV
        rows = jnp.arange(M, dtype=jnp.int32)
        pos = n0 + rows
        kpos = jnp.arange(S, dtype=jnp.int32)
        fed = (rows < n_fed)[:, None, None]
        x = self._w(params["embed"][tok], mode, 1)
        qb = min(self.Q_BLOCK, M)
        nb = M // qb

        def mm(a, w):
            return jnp.dot(a, w, precision=HIGHEST)

        def layer(x, xs):
            pl, ks, vs = xs
            h = _rms(x, pl["ln_attn"], eps)
            q = mm(h, self._w(pl["wq"], mode, 0))
            k = mm(h, self._w(pl["wk"], mode, 0))
            v = mm(h, self._w(pl["wv"], mode, 0))
            if s["bias"]:
                q, k, v = q + pl["bq"], k + pl["bk"], v + pl["bv"]
            q = _rope(q.reshape(M, H, hd), pos, s["theta"])
            k = _rope(k.reshape(M, KV, hd), pos, s["theta"])
            v = v.reshape(M, KV, hd)
            # an unfed row is the segment's last: no query of it is read
            ks = jax.lax.dynamic_update_slice_in_dim(
                ks, jnp.where(fed, k, 0.0), n0, axis=0)
            vs = jax.lax.dynamic_update_slice_in_dim(
                vs, jnp.where(fed, v, 0.0), n0, axis=0)
            qg = q.reshape(nb, qb, KV, G, hd)

            def block(args):
                qi, b = args
                sc = jnp.einsum("qngd,knd->ngqk", qi, ks,
                                precision=HIGHEST) / math.sqrt(hd)
                qp = n0 + b * qb + jnp.arange(qb)
                mask = kpos[None, :] <= qp[:, None]
                sc = jnp.where(mask[None, None], sc, -1e30)
                p = jax.nn.softmax(sc, axis=-1)
                return jnp.einsum("ngqk,knd->qngd", p, vs, precision=HIGHEST)

            o = jax.lax.map(block, (qg, jnp.arange(nb))).reshape(M, H * hd)
            x = x + mm(o, self._w(pl["wo"], mode, 0))
            h = _rms(x, pl["ln_ffn"], eps)
            g = mm(h, self._w(pl["w_gate"], mode, 0))
            u = mm(h, self._w(pl["w_up"], mode, 0))
            x = x + mm(jax.nn.silu(g) * u, self._w(pl["w_down"], mode, 0))
            return x, (ks, vs)

        x, (k_store, v_store) = jax.lax.scan(
            layer, x, (params["layers"], k_store, v_store))
        x = _rms(x[at], params["ln_f"], eps)
        # the head in vocabulary blocks, so that no float32 copy of the
        # whole embedding or head is ever held
        V = s["V"]
        nv = next(n for n in range(max(1, V * s["d"] // (1 << 27)), V + 1)
                  if V % n == 0)
        if s["tied"]:
            blocks = params["embed"].reshape(nv, V // nv, s["d"])

            def head_block(w):
                return mm(x, self._w(w, mode, 1).T)
        else:
            blocks = jnp.moveaxis(
                params["head"].reshape(s["d"], nv, V // nv), 1, 0)

            def head_block(w):
                return mm(x, self._w(w, mode, 0))
        out = jax.lax.map(head_block, blocks)          # (nv, A, V/nv)
        return (k_store, v_store,
                jnp.moveaxis(out, 0, 1).reshape(x.shape[0], V))
