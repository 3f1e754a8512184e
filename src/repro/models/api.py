"""Uniform model protocol consumed by the trainer, the server, the LLMS
context manager, and the dry-run driver.

Every family implements:

  init(key) -> params                        (pytree of stacked-layer arrays)
  loss(params, batch) -> (scalar, metrics)   (next-token CE; remat inside)
  prefill(params, batch, want_density) -> PrefillOut
  decode_step(params, tokens, cache, ..., want_density) -> DecodeOut
  kv_spec() -> KVSpec                        (declarative cache adapter)
  _build_cache(batch, seq, dtype, layout) -> cache  (pytree incl. 'pos')
  input_specs(shape) -> (entry_name, kwargs of ShapeDtypeStruct)

**The cache adapter protocol.**  ``kv_spec()`` returns the family's
:class:`~repro.models.kvspec.KVSpec`: cache leaf names/dims, chunkability,
recompute/batched/paged/quant capabilities, tolerance class and
compression floor for the Eq.-3 planner, and constant-size recurrent
state.  The serving layers consume ONLY the spec — there is no family
string dispatch and no per-family ``init_cache`` fork.  ``init_cache``
is concrete here: it validates the requested ``layout`` against
``spec.layouts`` (clean ``ValueError`` for undeclared capabilities),
applies ``spec.clamp_to_max_seq``, and delegates the allocation to the
family's ``_build_cache``.

The legacy ``supports_batched_decode`` / ``supports_quant_resident`` /
``supports_paged_pool`` class booleans are deprecation shims for one
release: reading them emits ``DeprecationWarning`` and answers from the
spec; an external family that still defines them as plain class
attributes gets a spec synthesized from those booleans by the default
``kv_spec()``.

Layer parameters are STACKED on a leading axis and consumed by
``jax.lax.scan`` so the lowered HLO stays one-layer-sized regardless of
depth (95-layer deepseek compiles as fast as 6-layer whisper).

Caches are plain pytrees with an integer ``pos`` leaf; ``decode_step``
returns the cache with ``pos + 1``. This makes the cache a first-class
jit argument: the dry-run lowers ``decode_step`` against a
ShapeDtypeStruct cache without allocating it.
"""
from __future__ import annotations

import warnings
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec
from repro.models.kvspec import KVSpec, LAYOUT_MIXED, LAYOUT_WINDOW

Array = jax.Array
PyTree = Any


class PrefillOut(NamedTuple):
    logits: Array                  # (B, vocab) -- last position only
    cache: PyTree
    density: Optional[PyTree]      # per-token Eq.-1 density, family-specific


class DecodeOut(NamedTuple):
    logits: Array                  # (B, vocab)
    cache: PyTree
    # routed models: the expert ids each row chose in each routed
    # layer, (L_moe, B, top_k) int32
    experts: Optional[Array] = None
    # where the family gives it: each sequence leaf's new row, (L, B,
    # ...), the token this round wrote into ``cache`` at ``pos``
    new_kv: Optional[PyTree] = None


def cross_entropy(logits: Array, targets: Array, mask: Optional[Array] = None
                  ) -> Tuple[Array, Dict[str, Array]]:
    """Token-mean CE in fp32. logits (B,S,V), targets (B,S) int32."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - ll
    if mask is None:
        mask = jnp.ones_like(nll)
    mask = mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    loss = jnp.sum(nll * mask) / denom
    acc = jnp.sum((jnp.argmax(logits, -1) == targets) * mask) / denom
    return loss, {"loss": loss, "acc": acc}


class _LegacyCapabilityFlag:
    """Deprecation shim: ``model.supports_*`` reads answer from the
    KVSpec and warn.  Subclasses that still assign a plain bool shadow
    the descriptor — the default ``kv_spec()`` picks those up."""

    def __init__(self, name: str, getter):
        self.name = name
        self.getter = getter

    def __get__(self, obj, objtype=None):
        warnings.warn(
            f"{self.name} is deprecated; query model.kv_spec() (or "
            "registry.family_spec(cfg)) instead", DeprecationWarning,
            stacklevel=2)
        if obj is None:
            return False
        return self.getter(obj.kv_spec())


def _legacy_flag(cls: type, name: str) -> bool:
    """A plain-bool ``supports_*`` override on a subclass (pre-KVSpec
    external family), skipping ModelBase's descriptors."""
    for klass in cls.__mro__:
        if klass is ModelBase:
            break
        val = klass.__dict__.get(name)
        if isinstance(val, bool):
            return val
    return False


class ModelBase:
    """Common plumbing; families override the layer stack."""

    # deprecation shims (one release): reads warn and proxy to kv_spec()
    supports_batched_decode = _LegacyCapabilityFlag(
        "supports_batched_decode", lambda s: s.batched_decode)
    supports_quant_resident = _LegacyCapabilityFlag(
        "supports_quant_resident", lambda s: s.quant_resident)
    supports_paged_pool = _LegacyCapabilityFlag(
        "supports_paged_pool", lambda s: s.paged)

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg

    # -- cache adapter ------------------------------------------------- #
    def kv_spec(self) -> KVSpec:
        """The family's declarative cache descriptor.  The default
        synthesizes a dense-shaped spec from legacy ``supports_*`` class
        booleans so external pre-KVSpec families keep working; every
        in-tree family overrides this."""
        cfg = self.cfg
        return KVSpec(
            family=cfg.family,
            seq_leaves=("k", "v"),
            leaf_dims={"k": (cfg.n_kv_heads, cfg.head_dim),
                       "v": (cfg.n_kv_heads, cfg.head_dim)},
            servable=hasattr(self, "recompute"),
            chunkable=True,
            recomputable=hasattr(self, "recompute"),
            batched_decode=_legacy_flag(type(self),
                                        "supports_batched_decode"),
            quant_resident=_legacy_flag(type(self),
                                        "supports_quant_resident"),
            paged=_legacy_flag(type(self), "supports_paged_pool"),
            layouts=((LAYOUT_WINDOW, LAYOUT_MIXED)
                     if _legacy_flag(type(self), "supports_quant_resident")
                     else (LAYOUT_WINDOW,)),
        )

    def init_cache(self, batch: int, seq: int, dtype=jnp.bfloat16,
                   layout: str = LAYOUT_WINDOW,
                   mixed_quant: Optional[bool] = None) -> PyTree:
        """Allocate a decode cache.  ``layout`` must be declared in
        ``kv_spec().layouts``; the legacy ``mixed_quant=`` kwarg maps to
        ``layout="mixed"`` with a DeprecationWarning."""
        if mixed_quant is not None:
            warnings.warn(
                "init_cache(mixed_quant=...) is deprecated; pass "
                "layout='mixed' / layout='window'", DeprecationWarning,
                stacklevel=2)
            layout = LAYOUT_MIXED if mixed_quant else LAYOUT_WINDOW
        spec = self.kv_spec()
        if layout not in spec.layouts:
            raise ValueError(
                f"family {spec.family!r} does not support cache layout "
                f"{layout!r} (declared layouts: {spec.layouts})")
        if spec.clamp_to_max_seq:
            seq = min(seq, self.cfg.max_seq)
        return self._build_cache(batch, seq, dtype, layout)

    def _build_cache(self, batch: int, seq: int, dtype, layout: str
                     ) -> PyTree:
        raise NotImplementedError

    # -- entry points ------------------------------------------------- #
    def init(self, key) -> PyTree:
        raise NotImplementedError

    def loss(self, params, batch) -> Tuple[Array, Dict[str, Array]]:
        raise NotImplementedError

    def prefill(self, params, batch, want_density: bool = False) -> PrefillOut:
        raise NotImplementedError

    def decode_step(self, params, tokens, cache) -> DecodeOut:
        raise NotImplementedError

    # -- dry-run specs ------------------------------------------------- #
    def batch_specs(self, shape: ShapeSpec) -> Dict[str, jax.ShapeDtypeStruct]:
        """ShapeDtypeStruct stand-ins for the data batch of this shape."""
        B, S = shape.global_batch, self.clamp_seq(shape.seq_len)
        tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
        return {"tokens": tok, "targets": tok}

    def clamp_seq(self, seq: int) -> int:
        if self.kv_spec().clamp_to_max_seq:
            return min(seq, self.cfg.max_seq)
        return seq

    def decode_seq(self, shape: ShapeSpec) -> int:
        return self.clamp_seq(shape.seq_len)

    def cache_specs(self, shape: ShapeSpec, dtype=jnp.bfloat16) -> PyTree:
        cache = jax.eval_shape(
            lambda: self.init_cache(shape.global_batch, self.decode_seq(shape),
                                    dtype))
        return cache

    def input_specs(self, shape: ShapeSpec
                    ) -> Tuple[str, Dict[str, Any]]:
        """(entry_point_name, kwargs-of-ShapeDtypeStruct) for the dry-run."""
        if shape.kind == "train":
            return "train", dict(batch=self.batch_specs(shape))
        if shape.kind == "prefill":
            b = self.batch_specs(shape)
            b.pop("targets")
            return "prefill", dict(batch=b)
        # decode: one new token against a seq_len-deep cache
        B = shape.global_batch
        return "decode", dict(
            tokens=jax.ShapeDtypeStruct((B, 1), jnp.int32),
            cache=self.cache_specs(shape),
        )

    # -- streaming (paper §4: sliding window + attention sinks) -------- #
    def streaming_window(self, shape: ShapeSpec) -> Tuple[int, int]:
        """(window, n_sinks) for this shape; (0, 0) = full attention."""
        cfg = self.cfg
        if shape.name == "long_500k" and self.kv_spec().streaming_long:
            return 8192, cfg.n_sink_tokens
        if cfg.sliding_window:
            return cfg.sliding_window, cfg.n_sink_tokens
        return 0, 0
