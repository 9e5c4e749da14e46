"""The absorbed MLA decode over a slot cache of latent rows, reading only
the rows that hold tokens.

Every head's query is folded into latent space (`ops/mla.py`), so a slot's
Q x H query rows (Q new positions: 1, or 2 when a draft is verified) share
ONE key row per cached position, `[c, k_r]` in W lanes, and its first
`rank` lanes are the value: the block is fetched once and used for both
products. At 128 heads x 2 positions that is 256 query rows against a block,
~240 FLOP per byte read: the MXU's work, not the memory's.

The walk is `decode_attention.live_blocks`' (busy slots first; a block past
a slot's last live one repeats an index, so it is not fetched) with this
kernel's own block height. The new positions' own rows are not in the cache
yet: they close the online softmax at the slot's last grid step, position a
seeing `cur[:a + 1]` (causal among the new ones), so an idle slot (length
0) gives its self terms alone, finite.

Precision is that of the einsum form in `ops/mla.py`, this kernel's
reference: bf16 operands, float32 scores and softmax statistics,
probabilities cast to the cache's type before the value product.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import _util, decode_attention

_NEG_INF = -1e30
# rows of the latent cache per grid step: 512 x 640 lanes x bf16 = 0.66 MB,
# 0.8 us of HBM time and ~1.7 us of MXU time at 256 query rows, against
# ~0.35 us for an empty grid step
_BLOCK_ROWS = 512


def block_rows(attn_len: int) -> int:
    return min(_BLOCK_ROWS, attn_len)


def uses_kernel(cache: jax.Array, attn_len: int, n_heads: int) -> bool:
    """Whether the absorbed decode runs this kernel over `cache`
    [L, B, 1, max_len, W]: on a TPU, where the row fills whole lanes, each
    position's heads fill whole sublanes and the blocks tile the window."""
    rows = block_rows(attn_len)
    return (_util.on_tpu() and cache.shape[-1] % 128 == 0 and n_heads % 8 == 0
            and rows % (32 // cache.dtype.itemsize) == 0
            and attn_len % rows == 0)


def live_blocks(lengths: jax.Array, attn_len: int):
    return decode_attention.live_blocks(lengths, attn_len, block_rows(attn_len))


def _kernel(layer_ref, order_ref, rows_ref, src_ref, lo_ref, hi_ref,  # scalars
            q_ref, cur_ref, c_ref, o_ref, acc_ref, m_ref, l_ref, *,
            rows: int, rank: int, n_query: int, scale: float):
    i, j = pl.program_id(0), pl.program_id(1)
    n = rows_ref[i]
    q = q_ref[...]  # [Q H, W]

    @pl.when(j == 0)
    def _open():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def accumulate(partial: bool):
        k = c_ref[...]  # [rows, W]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        v = k[:, :rank]
        if partial:
            # the block holds the slot's last row: mask the scores past it,
            # and zero the values there (0 x whatever the row holds stays 0)
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * rows
            s = jnp.where(cols < n, s, _NEG_INF)
            v_rows = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0) + j * rows
            v = jnp.where(v_rows < n, v, jnp.zeros_like(v))
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    pl.when((j + 1) * rows <= n)(functools.partial(accumulate, False))
    pl.when(jnp.logical_and(j * rows < n, n < (j + 1) * rows))(
        functools.partial(accumulate, True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _close():
        heads = q.shape[0] // n_query
        for a in range(n_query):          # the new positions' own rows
            at = slice(a * heads, (a + 1) * heads)
            qa = q[at].astype(jnp.float32)
            m, l, acc = m_ref[at], l_ref[at], acc_ref[at]
            for c in range(a + 1):
                row = cur_ref[c].astype(jnp.float32)            # [1, W]
                s = jnp.sum(qa * row, axis=-1, keepdims=True) * scale
                m_new = jnp.maximum(m, s)
                alpha, p = jnp.exp(m - m_new), jnp.exp(s - m_new)
                l = l * alpha + p
                # the probability rounded as the blocks' are
                acc = acc * alpha + p.astype(c_ref.dtype).astype(jnp.float32) \
                    * row[:, :rank].astype(c_ref.dtype).astype(jnp.float32)
                m = m_new
            o_ref[at] = (acc / l).astype(o_ref.dtype)           # l >= ~1


def latent_decode_attention(q_lat: jax.Array, cur: jax.Array, cache: jax.Array,
                            layer, blocks, attn_len: int, rank: int,
                            scale: float) -> jax.Array:
    """q_lat [B, Q H, W] the queries in latent space (position-major); cur
    [B, Q, W] the new positions' own rows; cache [L, B, 1, max_len, W];
    `layer` a scalar; `blocks` = `live_blocks(lengths, attn_len)` ->
    [B, Q H, rank]: the softmax-weighted sum of the first `rank` lanes of
    the rows each query may see."""
    B, QH, W = q_lat.shape
    Q = cur.shape[1]
    rows = block_rows(attn_len)

    def per_slot(*shape):
        return pl.BlockSpec((None,) + shape,
                            lambda i, j, layer_ref, order_ref, *_:
                            (order_ref[i],) + (0,) * len(shape),
                            memory_space=pltpu.VMEM)

    def window(i, j, layer_ref, order_ref, rows_ref, src_ref, lo_ref, hi_ref):
        return (layer_ref[0], src_ref[i], 0,
                jnp.clip(j, lo_ref[i], hi_ref[i]), 0)

    return pl.pallas_call(
        functools.partial(_kernel, rows=rows, rank=rank, n_query=Q, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=6,
            grid=(B, attn_len // rows),
            in_specs=[per_slot(QH, W), per_slot(Q, 1, W),
                      pl.BlockSpec((None, None, None, rows, W), window,
                                   memory_space=pltpu.VMEM)],
            out_specs=per_slot(QH, rank),
            scratch_shapes=[pltpu.VMEM((QH, rank), jnp.float32),
                            pltpu.VMEM((QH, 1), jnp.float32),
                            pltpu.VMEM((QH, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, QH, rank), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="mla_decode_attention",
        interpret=_util.interpret_mode(),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *blocks,
      q_lat, cur[:, :, None], cache)
