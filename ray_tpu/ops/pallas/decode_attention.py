"""Single-token grouped-query attention over a slot cache, reading only the
rows that hold tokens.

The decode step's attention used to contract against one window
`[slots, kvh, attn_len, hd]` per layer, `attn_len` being the bucket of the
DEEPEST busy slot, behind a mask: at 7-9 busy slots of 32 with ~330 rows
each under a bucket of 512 or 1024, five sixths of what it read was masked
away (PERF.md, PR 29). This kernel takes the WHOLE caches
`[L, B, kvh, max_len, hd]`: a `dynamic_slice` of the layer's window cannot
fuse into a Mosaic call, so XLA:TPU would copy the window first, twice a
layer. The caches stay in HBM; the layer index and the walk go in as
prefetched scalars.

One call a layer, no grid: the kernel loops over the (slot, block of R rows)
items that hold a row (`live_items`: slots ascending, a slot's blocks
ascending) and over nothing else. It copies each item's `[kvh, R, hd]` block
of K and of V into one of two VMEM buffers with its own DMA, item n + 1 in
flight while item n is multiplied. The item that opens a slot starts the
online softmax from the self term, the one that closes it divides and writes
the slot's output; only a slot's last block is masked. q, the current rows
and the output are whole in VMEM. Up to PR 44 this was a grid of (slots,
blocks of the window), a sequential step of ~0.3 us each whether or not the
block held a row: at 4 busy slots of 32 under a bucket of 1024, 128 steps a
layer for 8 blocks read (PERF.md, PR 45).

Precision is that of `models/inference.py:_gqa_decode_attention`, the CPU
path and this kernel's reference: bf16 operands, float32 scores and
softmax statistics, probabilities cast to the cache dtype before x V. The
current token's own K/V row is not in the cache yet (STRICT mask); it
starts the online softmax, and an idle slot's output is its self term alone
(`v_cur`), finite: every slot's output is set to it before the walk.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import _util

_NEG_INF = -1e30
# rows of K and of V per item. At 8 kv heads x 128 x bf16 a block of 256 is
# 0.5 MB of each, 1.3 us of HBM time, about what its two products take; an
# item costs ~0.3 us besides (its scalars, two DMA descriptors, two waits),
# and a block past a slot's last row is read and masked. Measured in the
# whole step at 32 slots x 1024 (my chip runs, PR 45, call 1; ms at 8 busy
# slots of 330 rows under 512 / 8 under 1024 / 23 under 1024 / all 32 full):
# 128 rows 5.47 / 5.48 / 6.46 / 10.11, 256: 5.53 / 5.53 / 6.60 / 9.22, 512:
# 5.53 / 5.53 / 6.61 / 9.22. 128 wastes the fewest rows past a length and
# pays twice the items where slots are deep (and 6.45 against 6.19 at 32
# slots under a bucket of 256); 512 wastes the most on ragged lengths. At
# one kv head (Jamba: 64 KB a block) the item's cost is all there is: 256
# slots x 1023 rows read 991 / 560 / 345 us a call
_BLOCK_ROWS = 256


def block_rows(attn_len: int) -> int:
    return min(_BLOCK_ROWS, attn_len)


def uses_decode_kernel(cache: jax.Array, attn_len: int) -> bool:
    """Whether the decode step runs this kernel over `cache`
    [L, B, kvh, max_len, hd] for a window of `attn_len` rows: on a TPU, at
    shapes that tile (lanes of 128; blocks of whole packed sublanes that
    divide the window)."""
    rows = block_rows(attn_len)
    return (_util.on_tpu() and cache.shape[-1] % 128 == 0
            and rows % (32 // cache.dtype.itemsize) == 0
            and attn_len % rows == 0)


def live_blocks(lengths: jax.Array, attn_len: int, rows: int = 0):
    """A grid kernel's walk over the cache (`mla_decode`'s; this module's own
    kernel walks `live_items`), as five [B] int32 arrays indexed by grid
    step i of the slot axis: `order` (the slot served: busy slots
    first, so that one block's arithmetic hides the next one's DMA), `rows`
    (that slot's length), and (`src`, `lo`, `hi`): step (i, j) fetches block
    `clip(j, lo[i], hi[i])` of slot `src[i]`. A busy slot walks its own
    blocks up to the last that holds a row; the idle ones behind them stay
    on the last busy slot's last block, which is already in VMEM.
    Loop-invariant over the layers: computed once a step. `rows`: another block height."""
    rows = rows or block_rows(attn_len)
    B = lengths.shape[0]
    live = lengths > 0
    order = jnp.argsort(~live, stable=True)
    last = jnp.clip((lengths - 1) // rows, 0, attn_len // rows - 1)[order]
    step = jnp.arange(B)
    n_live = jnp.sum(live)
    at = jnp.minimum(step, jnp.maximum(n_live - 1, 0))  # the last busy step
    hi = last[at]
    return tuple(a.astype(jnp.int32) for a in (
        order, lengths[order], order[at], jnp.where(step < n_live, 0, hi), hi))


def live_items(lengths: jax.Array, attn_len: int, rows: int = 0):
    """The kernel's walk over the cache as a list: the (slot, block) pairs
    that hold a row, slots ascending and a slot's blocks ascending, as
    (`slot` [N], `block` [N], `count` [1], `held` [B]), all int32, with
    N = B x attn_len / rows the most there can be and `held` each slot's
    rows inside the window. An idle slot has no item; entries at and past
    `count` are never read. Loop-invariant over the layers: computed once a
    step. `rows`: another block height."""
    rows = rows or block_rows(attn_len)
    B = lengths.shape[0]
    held = jnp.minimum(lengths, attn_len)
    n_blocks = (held + rows - 1) // rows
    ends = jnp.cumsum(n_blocks)
    item = jnp.arange(B * (attn_len // rows))
    # the slot whose run of items holds item n: B x N compares, no loop
    slot = jnp.minimum(jnp.searchsorted(ends, item, side="right",
                                        method="compare_all"), B - 1)
    block = jnp.where(item < ends[-1], item - (ends - n_blocks)[slot], 0)
    return tuple(a.astype(jnp.int32) for a in (slot, block, ends[-1:], held))


def _kernel(layer_ref, slot_ref, block_ref, count_ref, held_ref,  # scalars
            q_ref, kc_ref, vc_ref, k_hbm, v_hbm, o_ref,
            k_buf, v_buf, sem, acc_ref, m_ref, l_ref, *, rows: int, scale: float,
            skip_ref=None):
    """`skip_ref` [B] (a RING of rows that has wrapped, `gqa_decode_attention`'s
    `skip`): the one row of each slot that is not attended, -1 for none."""
    layer, count = layer_ref[0], count_ref[0]

    def copies(item, buf):
        """Item `item`'s block of K and of V, HBM -> VMEM buffer `buf`."""
        at = (layer, slot_ref[item], slice(None),
              pl.ds(pl.multiple_of(block_ref[item] * rows, rows), rows))
        return (pltpu.make_async_copy(k_hbm.at[at], k_buf.at[buf], sem.at[0, buf]),
                pltpu.make_async_copy(v_hbm.at[at], v_buf.at[buf], sem.at[1, buf]))

    def start(item, buf):
        for copy in copies(item, buf):
            copy.start()

    pl.when(count > 0)(lambda: start(0, 0))
    # an idle slot's output is its self term alone, the softmax over one
    # score: v_cur. Every slot starts there; a busy one is overwritten when
    # its last item closes it
    o_ref[...] = jnp.broadcast_to(vc_ref[...][:, :, None], o_ref.shape
                                  ).astype(o_ref.dtype)

    def walk(item, _):
        buf = item % 2
        pl.when(item + 1 < count)(lambda: start(item + 1, 1 - buf))
        b, j = slot_ref[item], block_ref[item]
        n = held_ref[b]
        q = q_ref[b]  # [kvh, rep, hd]

        @pl.when(j == 0)
        def _self_term():
            # the current token's own row opens the online softmax: m = its
            # score, l = 1, acc = 1 x v_cur
            s = jnp.sum(q.astype(jnp.float32) * kc_ref[b][:, None],
                        axis=-1, keepdims=True) * scale
            m_ref[...] = s
            l_ref[...] = jnp.ones_like(l_ref)
            acc_ref[...] = jnp.broadcast_to(vc_ref[b][:, None], acc_ref.shape)

        for copy in copies(item, buf):
            copy.wait()

        def accumulate(partial: bool):
            k, v = k_buf[buf], v_buf[buf]  # [kvh, rows, hd]
            s = jnp.einsum("grd,gld->grl", q, k,
                           preferred_element_type=jnp.float32) * scale
            if partial:
                # the block holds the slot's last row: mask the scores past
                # it, and zero V there (0 x whatever the row holds stays 0)
                cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) + j * rows
                live = cols < n
                if skip_ref is not None:
                    live = jnp.logical_and(live, cols != skip_ref[b])
                s = jnp.where(live, s, _NEG_INF)
                v_rows = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1) + j * rows
                kept = v_rows < n
                if skip_ref is not None:
                    kept = jnp.logical_and(kept, v_rows != skip_ref[b])
                v = jnp.where(kept, v, jnp.zeros_like(v))
            m_prev = m_ref[...]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
                "grl,gld->grd", p.astype(v.dtype), v,
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

        if skip_ref is None:
            pl.when((j + 1) * rows <= n)(functools.partial(accumulate, False))
            pl.when((j + 1) * rows > n)(functools.partial(accumulate, True))
        else:   # any block may hold the row that has left the window
            accumulate(True)

        @pl.when((j + 1) * rows >= n)
        def _close():
            o_ref[b] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)  # l >= 1

    jax.lax.fori_loop(0, count, walk, None)


def gqa_decode_attention(q: jax.Array, k_cur: jax.Array, v_cur: jax.Array,
                         k_all: jax.Array, v_all: jax.Array, layer: jax.Array,
                         items, attn_len: int, sm_scale: float = 0.0,
                         skip: jax.Array = None) -> jax.Array:
    """q [B, kvh, rep, hd]; k_cur / v_cur [B, kvh, hd] (the current token's
    row, not in the cache yet); k_all / v_all [L, B, kvh, max_len, hd];
    `layer` a scalar; `items` = `live_items(lengths, attn_len)` ->
    [B, kvh, rep, hd]: slot b attends rows [0, min(lengths[b], attn_len)) of
    layer `layer` and its own row, under a softmax of `sm_scale` q . k
    (0: 1 / sqrt(hd)). `skip` [B] int32, for a cache whose rows are a RING
    (`attn_len` rows a slot, position n at row n % attn_len): the row of
    each slot that has left the window as the current one enters (n %
    attn_len once the ring has wrapped), -1 where none has."""
    B, kvh, rep, hd = q.shape
    rows = block_rows(attn_len)
    # the query group fills whole sublanes of the float32 statistics
    rep_pad = _util.round_up(rep, 8)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, rep_pad - rep), (0, 0)))
    # the current rows go in as float32 [B, kvh, hd], a tile a slot at 8 kv
    # heads: as bf16 [B, kvh, 1, hd] every row was a tile of its own, 1 MB
    # each for 32 slots where 128 KB do (0.02 ms a step, call 4)
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    kernel = functools.partial(_kernel, rows=rows, scale=sm_scale or hd ** -0.5)
    if skip is not None:
        items = tuple(items) + (skip.astype(jnp.int32),)
        ring = kernel
        kernel = lambda *refs: ring(*refs[:5], *refs[6:], skip_ref=refs[5])
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(items) + 1,
            grid=(),
            in_specs=[whole, whole, whole, in_hbm, in_hbm],
            out_specs=whole,
            scratch_shapes=[pltpu.VMEM((2, kvh, rows, hd), k_all.dtype),
                            pltpu.VMEM((2, kvh, rows, hd), v_all.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.VMEM((kvh, rep_pad, hd), jnp.float32),
                            pltpu.VMEM((kvh, rep_pad, 1), jnp.float32),
                            pltpu.VMEM((kvh, rep_pad, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        name="gqa_decode_attention",
        interpret=_util.interpret_mode(),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *items,
      q, k_cur.astype(jnp.float32), v_cur.astype(jnp.float32), k_all, v_all)
    return out[:, :, :rep]
