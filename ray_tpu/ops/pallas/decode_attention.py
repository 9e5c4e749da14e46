"""Single-token grouped-query attention over a slot cache, reading only the
rows that hold tokens.

The decode step's attention used to contract against one window
`[slots, kvh, attn_len, hd]` per layer, `attn_len` being the bucket of the
DEEPEST busy slot, behind a mask: at 7-9 busy slots of 32 with ~330 rows
each under a bucket of 512 or 1024, five sixths of what it read was masked
away (PERF.md, PR 29). This kernel takes `lengths` and the WHOLE caches
`[L, B, kvh, max_len, hd]`: a `dynamic_slice` of the layer's window cannot
fuse into a Mosaic call, so XLA:TPU would copy the window first, twice a
layer. The layer index and the lengths go in as prefetched scalars and the
K/V blocks' `index_map` reads them.

Grid (slot, block of R rows of the window), both sequential, the busy
slots first. A block past a slot's last live one repeats that block's index,
and an idle slot (length 0) repeats the index of the block fetched before
it: the pipeline issues a DMA only when the index changes, so neither is
read. The body runs under `pl.when(block * R < lengths[b])`.

Precision is that of `models/inference.py:_gqa_decode_attention`, the CPU
path and this kernel's reference: bf16 operands, float32 scores and
softmax statistics, probabilities cast to the cache dtype before x V. The
current token's own K/V row is not in the cache yet (STRICT mask); it
starts the online softmax, so an idle slot's output is its self term alone
(`v_cur`), finite.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import _util

_NEG_INF = -1e30
# rows of K and of V per grid step. At 8 kv heads x 128 x bf16 a block of 256
# is 0.5 MB of each, 1.3 us of HBM time against ~0.25 us for an empty grid
# step. Measured in the whole step at 32 slots x 1024 (my chip runs, PR 29;
# ms at 8 busy slots under 512 / 8 under 1024 / 23 / all 32 full): 128 rows
# 7.47 / 7.93 / 9.08 / 11.70, 256: 7.23 / 7.55 / 8.84 / 10.73, 512: 7.19 /
# 7.50 / 9.04 / 10.74: the larger block wastes more rows past each length
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
    """The kernel's walk over the cache, as five [B] int32 arrays indexed by
    grid step i of the slot axis: `order` (the slot served: busy slots
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


def _kernel(layer_ref, order_ref, rows_ref, src_ref, lo_ref, hi_ref,  # scalars
            q_ref, kc_ref, vc_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, rows: int, scale: float):
    i, j = pl.program_id(0), pl.program_id(1)
    n = rows_ref[i]
    q = q_ref[...]  # [kvh, rep, hd]

    @pl.when(j == 0)
    def _self_term():
        # the current token's own row opens the online softmax: m = its
        # score, l = 1, acc = 1 x v_cur
        s = jnp.sum(q.astype(jnp.float32) * kc_ref[...].astype(jnp.float32),
                    axis=-1, keepdims=True) * scale
        m_ref[...] = s
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.broadcast_to(vc_ref[...].astype(jnp.float32),
                                        acc_ref.shape)

    def accumulate(partial: bool):
        k, v = k_ref[...], v_ref[...]  # [kvh, rows, hd]
        s = jnp.einsum("grd,gld->grl", q, k,
                       preferred_element_type=jnp.float32) * scale
        if partial:
            # the block holds the slot's last row: mask the scores past it,
            # and zero V there (0 x whatever the row holds must stay 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2) + j * rows
            s = jnp.where(cols < n, s, _NEG_INF)
            v_rows = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1) + j * rows
            v = jnp.where(v_rows < n, v, jnp.zeros_like(v))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.einsum(
            "grl,gld->grd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    pl.when((j + 1) * rows <= n)(functools.partial(accumulate, False))
    pl.when(jnp.logical_and(j * rows < n, n < (j + 1) * rows))(
        functools.partial(accumulate, True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)  # l >= 1


def gqa_decode_attention(q: jax.Array, k_cur: jax.Array, v_cur: jax.Array,
                         k_all: jax.Array, v_all: jax.Array, layer: jax.Array,
                         blocks, attn_len: int) -> jax.Array:
    """q [B, kvh, rep, hd]; k_cur / v_cur [B, kvh, hd] (the current token's
    row, not in the cache yet); k_all / v_all [L, B, kvh, max_len, hd];
    `layer` a scalar; `blocks` = `live_blocks(lengths, attn_len)` ->
    [B, kvh, rep, hd]: slot b attends rows [0, min(lengths[b], attn_len)) of
    layer `layer` and its own row."""
    B, kvh, rep, hd = q.shape
    rows = block_rows(attn_len)
    # the query group fills whole sublanes of the float32 statistics
    rep_pad = _util.round_up(rep, 8)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, rep_pad - rep), (0, 0)))

    def per_slot(width):
        return pl.BlockSpec((None, kvh, width, hd),
                            lambda i, j, layer_ref, order_ref, *_:
                            (order_ref[i], 0, 0, 0),
                            memory_space=pltpu.VMEM)

    def window(i, j, layer_ref, order_ref, rows_ref, src_ref, lo_ref, hi_ref):
        return (layer_ref[0], src_ref[i], 0,
                jnp.clip(j, lo_ref[i], hi_ref[i]), 0)

    cache_spec = pl.BlockSpec((None, None, kvh, rows, hd), window,
                              memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, rows=rows, scale=hd ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(B, attn_len // rows),
            in_specs=[per_slot(rep_pad), per_slot(1), per_slot(1),
                      cache_spec, cache_spec],
            out_specs=per_slot(rep_pad),
            scratch_shapes=[pltpu.VMEM((kvh, rep_pad, hd), jnp.float32),
                            pltpu.VMEM((kvh, rep_pad, 1), jnp.float32),
                            pltpu.VMEM((kvh, rep_pad, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="gqa_decode_attention",
        interpret=_util.interpret_mode(),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *blocks,
      q, k_cur[:, :, None], v_cur[:, :, None], k_all, v_all)
    return out[:, :, :rep]
