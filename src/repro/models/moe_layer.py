"""Mixture-of-experts FFN with dropless, per-token routing.

Every token is routed on its own: router scores over all experts
(softmax), the greedy top-k of them, weighted by those scores as they
are.  The (token, expert) pairs are sorted by expert and the experts
run as one grouped matmul (``jax.lax.ragged_dot``: each expert
multiplies exactly the rows routed to it), then each token sums its k
expert outputs, weighted, in its own top-k order.  No token is ever
dropped and nothing about a token's result depends on which other
tokens share its batch, so prefill, decode and the Fig.-7 recompute of
a context give the same latents whatever else the service is running.
A fused always-on shared expert is added where the configuration has
one.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig

Array = jax.Array


def route(h: Array, router: Array, moe: MoEConfig) -> Tuple[Array, Array]:
    """h (T, d) -> (weights (T, k) f32, expert ids (T, k) int32): the
    top-k experts of each token by softmax router score, in descending
    order."""
    logits = h.astype(jnp.float32) @ router.astype(jnp.float32)
    w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), moe.top_k)
    return w, idx.astype(jnp.int32)


def moe_ffn(x: Array, p: Dict[str, Array], moe: MoEConfig,
            layer=None) -> Tuple[Array, Array]:
    """x: (..., d).  p: router (d,E), w_gate/w_up (E,d,fe), w_down (E,fe,d),
    optional s_gate/s_up (d,ds), s_down (ds,d) fused shared expert.
    With ``layer`` (a layer index, traced or not) the expert weights are
    a stack of layers flattened to (n*E, ...) and this layer's experts
    are groups ``layer*E ..``: the grouped matmul then takes the whole
    stack as it lies, where XLA would copy a per-layer slice of it.
    Returns (out (..., d), expert ids (T, k) int32 of the T = prod(...)
    tokens)."""
    orig_shape = x.shape
    d = orig_shape[-1]
    xt = x.reshape(-1, d)
    T, k = xt.shape[0], moe.top_k
    w, idx = route(xt, p["router"], moe)
    flat = idx.reshape(-1)                                   # (T*k,)
    order = jnp.argsort(flat, stable=True)
    if layer is not None:
        flat = flat + layer * moe.n_experts
    sizes = jnp.zeros((p["w_gate"].shape[0],), jnp.int32).at[flat].add(1)
    xs = xt[order // k]                                      # (T*k, d)

    def gmm(a, w):
        return jax.lax.ragged_dot(a, w, sizes)
    h = jax.nn.silu(gmm(xs, p["w_gate"])) * gmm(xs, p["w_up"])
    out = gmm(h.astype(x.dtype), p["w_down"])
    # back to (token, its j-th expert) order; the k-sum runs in that
    # fixed order, so a row's result does not depend on the batch
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(T * k, dtype=order.dtype))
    out = out[inv].reshape(T, k, d).astype(jnp.float32)
    y = jnp.einsum("tkd,tk->td", out, w).astype(x.dtype)
    y = y.reshape(orig_shape)
    if "s_gate" in p:
        y = y + (jax.nn.silu(x @ p["s_gate"]) * (x @ p["s_up"])) @ p["s_down"]
    return y, idx


def init_moe_params(key, d: int, moe: MoEConfig, dtype=jnp.bfloat16,
                    n_layers: int = 1) -> Dict[str, Array]:
    """Stacked (L, ...) MoE FFN params."""
    ks = jax.random.split(key, 6)
    E, fe = moe.n_experts, moe.d_expert
    L = n_layers
    scale = 0.02

    def lin(k, shape):
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(dtype)

    p = {
        "router": lin(ks[0], (L, d, E)).astype(jnp.float32),
        "w_gate": lin(ks[1], (L, E, d, fe)),
        "w_up": lin(ks[2], (L, E, d, fe)),
        "w_down": lin(ks[3], (L, E, fe, d)),
    }
    if moe.d_shared:
        p["s_gate"] = lin(ks[4], (L, d, moe.d_shared))
        p["s_up"] = lin(ks[5], (L, d, moe.d_shared))
        p["s_down"] = lin(ks[4], (L, moe.d_shared, d))
    return p
