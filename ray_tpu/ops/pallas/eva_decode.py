"""One position a slot of EVA attention (`ops/eva.py`) over a slot table of
two regions, reading only the rows that are live.

The table `[L, B, H, W + S, hd]` holds, a slot and layer, the open window's
rows in [0, W) (row n % W; a region the slot reuses every W positions) and a
summary a closed chunk in [W, W + S). The query at position n reads the
first n % W rows of the first region and the first (n // W) (W / C) of the
second, under ONE online softmax that its own row opens (STRICT: that row
is not in the table yet). As `decode_attention.gqa_decode_attention` the
kernel takes the WHOLE table with the layer as a prefetched scalar (a sliced
table would be copied first) and walks (slot, step), busy slots first; a
step is a block of R rows of either region, and a step with nothing live
repeats the block fetched before it, so the pipeline moves no byte for it.

Every head has keys of its own (no query group shares a row), so the
kernel moves 2 x hd x 2 bytes a head and row for 4 hd operations: it is
bound by the table's bytes by nature. The query's one row is padded to the
eight sublanes of the float32 statistics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import _util

_NEG_INF = -1e30
# rows of K and of V a grid step: at 32 heads x 128 x bf16 a block of 256 is
# 2 MB of each (2.6 us of HBM time apiece against ~0.35 us for an empty step)
_BLOCK_ROWS = 256
_VMEM_LIMIT = 48 * 2**20


def block_rows(window: int) -> int:
    return min(_BLOCK_ROWS, window)


def _steps(window: int, chunk: int, attn_len: int):
    """(steps over the window region, steps over the summaries) that a slot
    whose deepest position is under `attn_len` can need."""
    rows = block_rows(window)
    return (-(-min(attn_len, window) // rows), -(-(attn_len // chunk) // rows))


def uses_decode_kernel(table: jax.Array, window: int, chunk: int) -> bool:
    """Whether the step runs this kernel over `table` [L, B, H, W + S, hd]:
    on a TPU, lanes of 128, and both regions whole blocks of whole packed
    sublanes."""
    rows = block_rows(window)
    return (_util.on_tpu() and table.shape[-1] % 128 == 0
            and rows % (32 // table.dtype.itemsize) == 0
            and window % rows == 0 and table.shape[3] % rows == 0)


def live_blocks(lengths: jax.Array, window: int, chunk: int, attn_len: int):
    """The kernel's walk, loop-invariant over the layers. `order` [B]: the
    slot grid row i serves, busy slots first. Three flat [B x J] arrays over
    (row i, step j), J = the window region's steps then the summaries':
    `valid`, the live rows of the step's block (0: nothing to do); `src` and
    `blk`, the slot and block the step fetches: its own where it has live
    rows, else those of the last step before it that had (the first such
    step, for the steps before any)."""
    rows = block_rows(window)
    jw, js = _steps(window, chunk, attn_len)
    B, J = lengths.shape[0], jw + js
    order = jnp.argsort(~(lengths > 0), stable=True)
    n = lengths[order]
    j = jnp.arange(J)
    first = jnp.where(j < jw, j, j - jw) * rows              # row of its region
    count = jnp.where(j[None] < jw, (n % window)[:, None],
                      ((n // window) * (window // chunk))[:, None])
    valid = jnp.clip(count - first[None], 0, rows).reshape(-1)
    step = jnp.arange(B * J)
    last = jax.lax.cummax(jnp.where(valid > 0, step, -1))
    at = jnp.where(last >= 0, last, jnp.argmax(valid > 0))
    blk = jnp.where(j < jw, j, window // rows + j - jw)
    return tuple(a.astype(jnp.int32) for a in (
        order, order[at // J], blk[at % J], valid))


def _kernel(layer_ref, order_ref, src_ref, blk_ref, valid_ref,  # scalars
            q_ref, kc_ref, vc_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, rows: int, scale: float):
    i, j = pl.program_id(0), pl.program_id(1)
    n = valid_ref[i * pl.num_programs(1) + j]
    q = q_ref[...]  # [H, 8, hd]

    @pl.when(j == 0)
    def _self_term():
        s = jnp.sum(q.astype(jnp.float32) * kc_ref[...].astype(jnp.float32),
                    axis=-1, keepdims=True) * scale
        m_ref[...] = s
        l_ref[...] = jnp.ones_like(l_ref)
        acc_ref[...] = jnp.broadcast_to(vc_ref[...].astype(jnp.float32),
                                        acc_ref.shape)

    def accumulate(partial: bool):
        k, v = k_ref[...], v_ref[...]  # [H, rows, hd]
        s = jnp.einsum("grd,gld->grl", q, k,
                       preferred_element_type=jnp.float32) * scale
        if partial:
            # rows past the live ones are stale: masked, and V zeroed there
            cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
            s = jnp.where(cols < n, s, _NEG_INF)
            v_rows = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
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

    pl.when(n == rows)(functools.partial(accumulate, False))
    pl.when(jnp.logical_and(n > 0, n < rows))(functools.partial(accumulate, True))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)  # l >= 1


def eva_decode_attention(q: jax.Array, k_cur: jax.Array, v_cur: jax.Array,
                         k_all: jax.Array, v_all: jax.Array, layer: jax.Array,
                         blocks, window: int, chunk: int,
                         attn_len: int) -> jax.Array:
    """q, k_cur, v_cur [B, H, hd]; k_all, v_all [L, B, H, W + S, hd]; `layer`
    a scalar; `blocks` = `live_blocks(lengths, window, chunk, attn_len)` ->
    [B, H, hd]: `ops.eva.decode_attention` of layer `layer`'s table."""
    B, H, hd = q.shape
    rows = block_rows(window)
    jw, js = _steps(window, chunk, attn_len)
    q = jnp.broadcast_to(q[:, :, None], (B, H, 8, hd))

    def per_slot(width):
        return pl.BlockSpec((None, H, width, hd),
                            lambda i, j, layer_ref, order_ref, *_:
                            (order_ref[i], 0, 0, 0),
                            memory_space=pltpu.VMEM)

    def block(i, j, layer_ref, order_ref, src_ref, blk_ref, valid_ref):
        at = i * (jw + js) + j
        return (layer_ref[0], src_ref[at], 0, blk_ref[at], 0)

    table_spec = pl.BlockSpec((None, None, H, rows, hd), block,
                              memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_kernel, rows=rows, scale=hd ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, jw + js),
            in_specs=[per_slot(8), per_slot(1), per_slot(1),
                      table_spec, table_spec],
            out_specs=per_slot(8),
            scratch_shapes=[pltpu.VMEM((H, 8, hd), jnp.float32),
                            pltpu.VMEM((H, 8, 1), jnp.float32),
                            pltpu.VMEM((H, 8, 1), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="eva_decode_attention",
        interpret=_util.interpret_mode(),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *blocks,
      q, k_cur[:, :, None], v_cur[:, :, None], k_all, v_all)
    return out[:, :, 0]
