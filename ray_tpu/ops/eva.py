"""EVA attention: every position of the query's own window exactly, and one
learned summary a chunk of every EARLIER window, under ONE softmax.

Positions count from 0; chunk c holds positions [C c, C c + C), window w
positions [W w, W w + W), W a multiple of C. A chunk's summary, from the
ROTATED keys, with two learned vectors a head (`phi`, `mu`):

    a_m = softmax over m in the chunk of (s phi . k_m)
    kbar_c = sum_m a_m k_m + mu          vbar_c = sum_m a_m v_m

The query at n (window w = n // W) scores s q_n . k_m for m in its own
window with m <= n, and s q_n . kbar_c for every chunk c of a window before
w. Windows do not slide: the first query of a window sees itself and the
summaries. Nobody sees a chunk's summary before its window has closed. With
W >= the sequence length this IS plain causal attention.

Three pieces, plain `jax.numpy` (softmax statistics float32):

`summarise`          whole chunks -> their summaries;
`window_attention`   one window of a prompt pass against its own positions
                     and a table of earlier summaries, query rows a block at
                     a time, so that nothing of size n x n exists: the prompt
                     pass walks the windows in order (`models/hybrid.py`);
`decode_attention`   one position a slot against the slot's live window rows
                     and visible summary rows of ONE table (the CPU's and
                     the odd shape's form; on the TPU the step reads live
                     rows only, `ops/pallas/eva_decode.py`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
_NEG_INF = -1e30


def summarise(k: jax.Array, v: jax.Array, phi: jax.Array, mu: jax.Array,
              chunk: int, sm_scale: float):
    """k, v [..., H, n C, hd] (whole chunks, keys rotated); phi, mu [H, hd]
    -> kbar, vbar [..., H, n, hd] in the rows' type."""
    lead, hd = k.shape[:-2], k.shape[-1]
    kc = k.reshape(lead + (-1, chunk, hd)).astype(F32)
    vc = v.reshape(lead + (-1, chunk, hd)).astype(F32)
    a = jax.nn.softmax(
        jnp.einsum("...hncd,hd->...hnc", kc, phi.astype(F32)) * sm_scale, axis=-1)
    kbar = jnp.einsum("...hnc,...hncd->...hnd", a, kc) + mu.astype(F32)[:, None, :]
    vbar = jnp.einsum("...hnc,...hncd->...hnd", a, vc)
    return kbar.astype(k.dtype), vbar.astype(v.dtype)


def window_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kbar: jax.Array, vbar: jax.Array, n_visible,
                     sm_scale: float, q_block: int = 512) -> jax.Array:
    """ONE window of a sequence: q, k, v [b, W, H, hd] at the window's
    positions (q and k rotated); kbar, vbar [b, H, S, hd] a table of
    summaries of which the first `n_visible` rows (a scalar, traced or not:
    the chunks of the windows before this one) are seen -> [b, W, H, hd].
    Query rows go `q_block` at a time against the window's keys up to the
    block's own end and the S summary columns, under one softmax."""
    W = q.shape[1]
    blk = min(q_block, W)
    seen = jnp.arange(kbar.shape[2]) < n_visible
    outs = []
    for start in range(0, W, blk):
        end = min(start + blk, W)
        exact = jnp.einsum("bqhd,bkhd->bhqk", q[:, start:end], k[:, :end]
                           ).astype(F32) * sm_scale
        ok = (start + jnp.arange(end - start))[:, None] >= jnp.arange(end)[None, :]
        pooled = jnp.einsum("bqhd,bhkd->bhqk", q[:, start:end], kbar
                            ).astype(F32) * sm_scale
        pr = jax.nn.softmax(jnp.concatenate(
            [jnp.where(ok[None, None], exact, _NEG_INF),
             jnp.where(seen[None, None, None], pooled, _NEG_INF)], axis=-1), axis=-1)
        pr = pr.astype(v.dtype)
        outs.append(jnp.einsum("bhqk,bkhd->bqhd", pr[..., :end], v[:, :end])
                    + jnp.einsum("bhqk,bhkd->bqhd", pr[..., end:], vbar))
    return jnp.concatenate(outs, axis=1)


def decode_attention(q: jax.Array, k_cur: jax.Array, v_cur: jax.Array,
                     k_tab: jax.Array, v_tab: jax.Array, window_rows: jax.Array,
                     summary_rows: jax.Array, window: int,
                     sm_scale: float) -> jax.Array:
    """One position a slot: q, k_cur, v_cur [B, H, hd] (the position's own
    row, not in the table yet); k_tab, v_tab [B, H, W + S', hd]: rows
    [0, W) the window region, of which slot b's first `window_rows[b]` are
    live, behind them summary rows, of which its first `summary_rows[b]` are
    visible -> [B, H, hd]. A slot with neither attends its own row alone."""
    r = jnp.arange(k_tab.shape[2])[None, :]
    live = jnp.where(r < window, r < window_rows[:, None],
                     r - window < summary_rows[:, None])            # [B, rows]
    sc = jnp.einsum("bhd,bhrd->bhr", q, k_tab).astype(F32) * sm_scale
    own = jnp.sum(q.astype(F32) * k_cur.astype(F32), axis=-1, keepdims=True) * sm_scale
    pr = jax.nn.softmax(jnp.concatenate(
        [jnp.where(live[:, None], sc, _NEG_INF), own], axis=-1), axis=-1)
    pr = pr.astype(v_tab.dtype)
    return (jnp.einsum("bhr,bhrd->bhd", pr[..., :-1], v_tab)
            + pr[..., -1:] * v_cur)
