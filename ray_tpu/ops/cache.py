"""Writes into a slot cache that stay in place on the TPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def write_rows(cache: jax.Array, rows: jax.Array,
                lengths: jax.Array) -> jax.Array:
    """The decode step's cache write: `rows[:, b]` goes to row `lengths[b]`
    of slot b in every layer and kv head. cache [L, B, kvh, max_len, hd],
    rows [L, B, kvh, hd], lengths [B] -> cache.

    Written as a read-modify-write of the tile-aligned block of R rows that
    holds the position, one slot at a time, R being the rows one HBM tile
    of the cache's dtype packs (8 / 16 / 32 for 4- / 2- / 1-byte elements).
    A window of ONE row makes XLA:TPU's layout assignment put the window's
    dimensions minor-most, and bridge that to the default layout of the
    donated parameter and the aliased output with a copy of the whole cache
    before and after the write (`copy.58/61/64/65` up to PR 26); a
    whole-tile window keeps the default layout and the update stays in place.

    A slot whose position is at or past max_len writes nothing. An idle
    slot of the dense engine has length 0 and stays there (its row 0 is
    rewritten every step and replaced whole at the next admission); the
    hybrid model's idle slots count on from 0."""
    L, B, kvh, max_len, hd = cache.shape
    R = min(32 // cache.dtype.itemsize, max_len)
    row_ids = jnp.arange(R)[:, None]

    def write_slot(b, cache):
        pos = lengths[b]
        # clamped by hand: XLA would clamp a block that overhangs max_len
        # silently, and the row would land one block off
        start = jnp.minimum(pos // R * R, max_len - R)
        at = (0, b, 0, start, 0)
        block = jax.lax.dynamic_slice(cache, at, (L, 1, kvh, R, hd))
        new = jax.lax.dynamic_slice(rows, (0, b, 0, 0), (L, 1, kvh, hd))
        block = jnp.where(row_ids == pos - start, new[:, :, :, None], block)
        return jax.lax.dynamic_update_slice(cache, block, at)

    return jax.lax.fori_loop(0, B, write_slot, cache)
