"""Writes into a slot cache that stay in place on the TPU."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import _util


def _tile_rows(cache: jax.Array) -> int:
    """R: the rows one HBM tile of the cache's dtype packs (8 / 16 / 32 for
    4- / 2- / 1-byte elements), the height of the block a row write moves."""
    return 32 // cache.dtype.itemsize


# the grid pipeline holds a slot's block twice as input and twice as output;
# under the 16 MB a v5e kernel may use, with room for the rows
_VMEM_BLOCKS_BYTES = 12 * 2**20


def uses_write_kernel(cache: jax.Array) -> bool:
    """Whether `write_rows` runs its kernel over `cache`
    [L, B, kvh, max_len, hd]: on a TPU, where whole blocks of R rows tile
    max_len (the block of a position near the end is then aligned too), the
    last dimension fills whole lanes and four blocks [L, kvh, R, hd] fit
    the kernel's VMEM. Mosaic takes a full-width block of 576 lanes, but
    XLA:TPU keeps such an array with the positions minor-most, and would
    copy the whole cache to the kernel's row-major operand and back
    (`copy.498` / `copy.509` at [2, 64, 1, 8192, 576], asked of the
    described compiler, PR 33): `HybridConfig.latent_width` therefore stores
    latent rows in whole tiles."""
    L, _, kvh, max_len, hd = cache.shape
    R = _tile_rows(cache)
    return (_util.on_tpu() and hd % 128 == 0 and max_len % R == 0
            and 4 * L * kvh * R * hd * cache.dtype.itemsize <= _VMEM_BLOCKS_BYTES)


def write_rows(cache: jax.Array, rows: jax.Array, lengths: jax.Array,
               writes: jax.Array = None) -> jax.Array:
    """The decode step's cache write: `rows[:, b]` goes to row `lengths[b]`
    of slot b in every layer and kv head. cache [L, B, kvh, max_len, hd],
    rows [L, B, kvh, hd], lengths [B] -> cache. A cache whose rows are not
    its positions (EVA's window region turns over, its summaries are a row
    a chunk) passes the ROW in `lengths`' place and says which slots write
    one: `writes` [B] bool (row 0 can then be written; without it a slot
    writes iff its row is not 0).

    Written as a read-modify-write of the tile-aligned block of R rows that
    holds the position. A window of ONE row makes XLA:TPU's layout
    assignment put the window's dimensions minor-most, and bridge that to
    the default layout of the donated parameter and the aliased output with
    a copy of the whole cache before and after the write (`copy.58/61/64/65`
    up to PR 26); a whole-tile window keeps the default layout and the
    update stays in place.

    One algorithm, two executions, chosen by what the code can see (as
    `decode_attention.uses_decode_kernel` chooses): on a TPU at shapes that
    tile, one Pallas call that moves the blocks of the slots that hold
    something, each in flight while its neighbour is selected
    (`_write_rows_kernel`); elsewhere a loop over the slots, one block
    after another (`_write_rows_loop`).

    A slot of length 0 holds nothing and writes nothing; a slot whose
    position is at or past max_len writes nothing either. An idle slot of
    the dense engine and of the runs form has length 0 and stays there; the
    hybrid model's idle slots count on from 0 and write rows nobody reads."""
    if uses_write_kernel(cache):
        return _write_rows_kernel(cache, rows, lengths, writes)
    return _write_rows_loop(cache, rows, lengths, writes)


def _write_rows_loop(cache, rows, lengths, writes=None):
    L, B, kvh, max_len, hd = cache.shape
    R = min(_tile_rows(cache), max_len)
    row_ids = jnp.arange(R)[:, None]

    def write_slot(b, cache):
        pos = lengths[b]
        # clamped by hand: XLA would clamp a block that overhangs max_len
        # silently, and the row would land one block off
        start = jnp.minimum(pos // R * R, max_len - R)
        at = (0, b, 0, start, 0)
        block = jax.lax.dynamic_slice(cache, at, (L, 1, kvh, R, hd))
        new = jax.lax.dynamic_slice(rows, (0, b, 0, 0), (L, 1, kvh, hd))
        # a row id is never negative: a slot that does not write (by
        # default: of length 0) selects nothing
        row = jnp.where(pos > 0 if writes is None else writes[b], pos - start, -1)
        block = jnp.where(row_ids == row, new[:, :, :, None], block)
        return jax.lax.dynamic_update_slice(cache, block, at)

    return jax.lax.fori_loop(0, B, write_slot, cache)


def _walk(lengths: jax.Array, max_len: int, R: int, writes=None):
    """The kernel's walk over the slots, as three [B] int32 arrays indexed
    by grid step (the idea of `decode_attention.live_blocks`): `src`, the
    slot whose block the step holds, the slots that write first and the
    others repeating the last of them; `blk`, that block's index along
    max_len; and `row`, the row of the block that takes the new one, -1 on
    a step that writes nothing. The pipeline moves a block only when its
    index changes, so a step that repeats one costs no DMA."""
    B = lengths.shape[0]
    if writes is None:
        writes = lengths > 0
    writes = writes & (lengths < max_len)
    order = jnp.argsort(~writes, stable=True)
    step = jnp.arange(B)
    n = jnp.sum(writes)
    src = order[jnp.minimum(step, jnp.maximum(n - 1, 0))]
    pos = jnp.clip(lengths[src], 0, max_len - 1)
    row = jnp.where(step < n, pos % R, -1)
    return tuple(a.astype(jnp.int32) for a in (src, pos // R, row))


def _kernel(src_ref, blk_ref, row_ref, rows_ref, cache_ref, out_ref):
    i = pl.program_id(0)
    row = row_ref[i]

    # a step that writes nothing leaves the block of the last one that did
    # where it is; step 0 always holds a block (with every slot idle, slot
    # 0's first) and passes it through if it has no row for it
    @pl.when(jnp.logical_or(row >= 0, i == 0))
    def _select():
        block = cache_ref[...]  # [L, kvh, R, hd]
        at = jax.lax.broadcasted_iota(jnp.int32, block.shape, 2) == row
        out_ref[...] = jnp.where(at, rows_ref[...][:, :, None, :], block)


def _write_rows_kernel(cache, rows, lengths, writes=None):
    L, B, kvh, max_len, hd = cache.shape
    R = _tile_rows(cache)
    new = pl.BlockSpec((L, None, kvh, hd),
                       lambda i, src, blk, row: (0, src[i], 0, 0),
                       memory_space=pltpu.VMEM)
    block = pl.BlockSpec((L, None, kvh, R, hd),
                         lambda i, src, blk, row: (0, src[i], 0, blk[i], 0),
                         memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(B,),
            in_specs=[new, block], out_specs=block),
        out_shape=jax.ShapeDtypeStruct(cache.shape, cache.dtype),
        # the cache is its own output: blocks nobody moves stay as they are
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="write_rows",
        interpret=_util.interpret_mode(),
    )(*_walk(lengths, max_len, R, writes), rows, cache)
