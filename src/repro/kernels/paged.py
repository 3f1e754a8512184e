"""Paged KV-pool gather/scatter primitives.

The unified KV pool (core/pagepool.py) stores chunk-granular pages in
fixed arenas shaped ``(L, P, cs, ...)`` — one page = one chunk's worth
of a cache leaf across all layers.  Decode/prefill entries consume the
pool through ``gather_pages``: a per-slot page-index row materializes
the SAME dense ``(L, B, S, ...)`` layout the slot-cache entry points
were built on, so the paged path is bit-identical to the slot path by
construction (identical values at every valid position; invalid
positions are masked to exactly zero weight by the attention mask
before they can contribute).

This is the blocked-jnp CPU mirror: XLA lowers the advanced-indexing
gather to a block copy per (layer, page).  On a TPU that view costs
several writes of a cache-sized buffer a round, so bf16 decode there
reads its pages in place instead (``kernels/paged_attn.py``, chosen by
``models/common.py::paged_decode_in_place``).
"""
from __future__ import annotations

import jax


def gather_pages(arena: jax.Array, tables: jax.Array) -> jax.Array:
    """Materialize per-slot views of the pool.

    arena: (L, P, cs, ...) page arena; tables: (B, C) int32 page
    indices (page 0 is the scratch/zero page — rows point chunks they
    don't own at it).  -> (L, B, C*cs, ...) dense cache leaf.
    """
    g = arena[:, tables]                       # (L, B, C, cs, ...)
    L, B, C, cs = g.shape[:4]
    return g.reshape(L, B, C * cs, *g.shape[4:])


def scatter_token(arena: jax.Array, pages: jax.Array, offs: jax.Array,
                  val: jax.Array) -> jax.Array:
    """Write one new token per slot back into its tail page.

    arena: (L, P, cs, ...); pages/offs: (B,) int32 (page index and
    in-page offset per slot); val: (L, B, ...).  Distinct slots own
    distinct pages so the scatter indices never collide, except on the
    scratch page 0 where padded rows land (their values are never
    attended, so the write order is irrelevant).
    """
    return arena.at[:, pages, offs].set(val)


def scatter_chunk(arena: jax.Array, page, blk: jax.Array) -> jax.Array:
    """Admit one chunk: blk (L, cs, ...) -> arena[:, page]."""
    return arena.at[:, page].set(blk)


def gather_chunk(arena: jax.Array, page) -> jax.Array:
    """Read one chunk's page back out: -> (L, cs, ...)."""
    return arena[:, page]
