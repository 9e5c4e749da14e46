"""Learned sparse attention: a lightning indexer chooses the rows a query
attends to (DeepSeek-Sparse-Attention's form over grouped-query attention).

Beside its K/V row every position leaves ONE indexer key `kI` [di]. A query
position t scores every causal row s <= t with J small heads,

    I[t, s] = sum_j wI[t, j] * ReLU(qI[t, j] . kI[s])        (float32 sums)

and attends, under one softmax shared by all its heads, to the `topk` rows
with the largest score only (to every row while t + 1 <= topk). The choice
is EXACT: the k best of the whole causal row, ties to the lower index
(`jax.lax.top_k`'s order).

`prompt_attention`   a whole prompt. One algorithm, two executions chosen by
                     what the code can see (`uses_prompt_kernels`): on a TPU
                     at shapes that tile, two kernels that never hold an
                     [n, n] score in HBM (`ops/pallas/dsa.py`: the k-th best
                     score of every query by a radix search over scores kept
                     in VMEM, then flash attention under the mask the scores
                     and that threshold give); elsewhere `chunk` queries at a
                     time: scores [chunk, n], `lax.top_k`, a masked softmax.
`decode_select`      one position a slot: the slot's live indexer keys are
                     scored, the best `topk` of them and the position's own
                     row (not in the cache yet) make the row LIST.
`decode_attention`   attention over that list: only the listed rows of the
                     cache are read (a gather here; on a TPU a kernel that
                     fetches each listed row by its own DMA).

The cache holds a position's keys and values as ONE block `[k ; v]`
[2 kvh, hd] (at 4 kv heads of 128 one (8, 128) tile of bf16: what a DMA can
name) so that a chosen position is one contiguous read, and its indexer key
in whole tiles of lanes (`key_width`)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops.pallas import _util
from ray_tpu.ops.pallas import dsa as kernels

F32 = jnp.float32


def key_width(di: int) -> int:
    """Lanes an indexer key is stored in: whole tiles of 128."""
    return _util.round_up(di, 128)


def index_scores(qi: jax.Array, wi: jax.Array, ki: jax.Array) -> jax.Array:
    """qi [..., t, J, di], wi [..., t, J] float32, ki [..., s, di] ->
    I [..., t, s] float32."""
    s = jnp.einsum("...tjd,...sd->...tjs", qi, ki, preferred_element_type=F32)
    return jnp.einsum("...tjs,...tj->...ts", jax.nn.relu(s), wi.astype(F32))


def top_rows(scores: jax.Array, k: int):
    """The exact top-k of every row: scores [..., n] float32, -inf where a
    row is no candidate -> (values [..., k] best first, rows [..., k]
    int32); ties go to the lower index."""
    vals, idx = jax.lax.top_k(scores, k)
    return vals, idx.astype(jnp.int32)


def pack_rows(chosen: jax.Array) -> jax.Array:
    """chosen [t, n] bool -> [ceil(t / 32), n] int32: bit t % 32 of word
    [t // 32, s] says that query t chose row s (what a comparison that
    follows the program's choice is handed: 32 times smaller than the
    mask)."""
    t, n = chosen.shape
    words = _util.cdiv(t, 32)
    bits = jnp.pad(chosen, ((0, words * 32 - t), (0, 0))).reshape(words, 32, n)
    bits = bits.astype(jnp.uint32) << jnp.arange(32, dtype=jnp.uint32)[None, :, None]
    return jax.lax.bitcast_convert_type(jnp.sum(bits, axis=1, dtype=jnp.uint32),
                                        jnp.int32)


def uses_prompt_kernels(s: int, hd: int) -> bool:
    """Whether a prompt of `s` positions runs the two kernels: on a TPU,
    whole blocks of the kernels' 512 keys and lanes of 128."""
    return _util.on_tpu() and s % kernels.KEY_BLOCK == 0 and hd % 128 == 0


def _chunked(q, k, v, qi, wi, ki, topk, chunk, scale, with_rows):
    """`prompt_attention` for one sequence, `chunk` queries at a time:
    q [s, H, hd], k, v [s, kvh, hd], qi [s, J, di], wi [s, J], ki [s, di]."""
    s, H, hd = q.shape
    kvh = k.shape[1]
    c = chunk if s % chunk == 0 else s
    cols = jnp.arange(s)

    def rows(c0):
        cut = lambda a: jax.lax.dynamic_slice_in_dim(a, c0, c, axis=0)
        t = c0 + jnp.arange(c)
        with jax.named_scope("select"):
            scores = jnp.where(cols[None, :] <= t[:, None],
                               index_scores(cut(qi), cut(wi), ki), -jnp.inf)
            vals, idx = top_rows(scores, min(topk, s))
            chosen = jnp.zeros((c, s), bool).at[jnp.arange(c)[:, None], idx].set(
                vals > -jnp.inf)
        with jax.named_scope("attend"):
            sc = jnp.einsum("tgrd,sgd->tgrs", cut(q).reshape(c, kvh, H // kvh, hd), k,
                            preferred_element_type=F32) * scale
            p = jax.nn.softmax(jnp.where(chosen[:, None, None], sc, -1e30), axis=-1)
            o = jnp.einsum("tgrs,sgd->tgrd", p.astype(v.dtype), v,
                           preferred_element_type=F32)
        return o.reshape(c, H, hd).astype(q.dtype), chosen

    o, chosen = jax.lax.map(rows, jnp.arange(0, s, c))
    return (o.reshape(s, H, hd),
            pack_rows(chosen.reshape(s, s)) if with_rows else None)


def prompt_attention(q, k, v, qi, wi, ki, topk: int, chunk: int, scale: float,
                     with_rows: bool = False):
    """Sparse causal attention of whole prompts. q [b, s, H, hd] and k, v
    [b, s, kvh, hd] rotated, in the cache's type; qi [b, s, J, di], wi
    [b, s, J] float32, ki [b, s, >= di] (stored lanes; the first di count)
    -> (o [b, s, H, hd], None or, with `with_rows`, the rows every query
    chose, packed [b, ceil(s / 32), s] int32: `pack_rows`). The caller runs
    plain causal attention where s <= topk: every row is chosen there."""
    di = qi.shape[-1]
    if uses_prompt_kernels(q.shape[1], q.shape[-1]):
        one = lambda a: kernels.prompt_attention(*a, topk=topk, scale=scale,
                                                 with_rows=with_rows)
    else:
        one = lambda a: _chunked(*a[:5], a[5][:, :di], topk, chunk, scale, with_rows)
    return jax.lax.map(one, (q, k, v, qi, wi.astype(F32), ki))


def decode_select(qi, wi, ki_cur, ik_all, layer, lengths, attn_len: int, topk: int):
    """One query position a slot against the slot's cached indexer keys:
    qi [B, J, di], wi [B, J], ki_cur [B, di] (the position's own key, not in
    the cache yet), ik_all [L, B, 1, max_len, W], lengths [B] -> (rows
    [B, K] int32, K = min(topk, attn_len): the chosen cached rows, best
    first; count [B]: how many of them count; own [B] bool: whether the
    position's own row is among its `topk` best). A slot of n < topk
    positions lists all n and its own row; one of n >= topk lists topk rows,
    or topk - 1 and its own row where that row scores above the list's
    last. An idle slot (length 0) lists none."""
    B, _, di = qi.shape
    K = min(topk, attn_len)
    wi = wi.astype(F32)
    if kernels.uses_scores_kernel(ik_all, attn_len):
        scores = kernels.decode_scores(qi, wi, ik_all, layer, lengths, attn_len)
    else:
        W = ik_all.shape[-1]
        keys = jax.lax.dynamic_slice(ik_all, (layer, 0, 0, 0, 0),
                                     (1, B, 1, attn_len, W))[0, :, 0, :, :di]
        scores = index_scores(qi[:, None], wi[:, None], keys)[:, 0]
        scores = jnp.where(jnp.arange(attn_len)[None, :] < lengths[:, None],
                           scores, -jnp.inf)
    vals, rows = top_rows(scores, K)
    mine = index_scores(qi[:, None], wi[:, None], ki_cur[:, None, :di])[:, 0, 0]
    full = lengths >= topk
    own = ~full | (mine > vals[:, K - 1])
    count = jnp.minimum(lengths, topk) - (full & own)
    return rows, count.astype(jnp.int32), own


def decode_attention(q, k_cur, v_cur, kv_all, layer, rows, count, own,
                     scale: float):
    """q [B, kvh, rep, hd]; k_cur, v_cur [B, kvh, hd] (the position's own
    row); kv_all [L, B, max_len, 2 kvh, hd] (a position's `[k ; v]`);
    `rows`, `count`, `own` as `decode_select` gives them -> o
    [B, kvh, rep, hd]: the softmax over the first `count` listed rows of
    layer `layer` and, where `own`, the position's own row. An idle slot's
    output is its own value row."""
    B, kvh, rep, hd = q.shape
    if kernels.uses_rows_kernel(kv_all, rows.shape[1]):
        return kernels.decode_attention(q, k_cur, v_cur, kv_all, layer, rows,
                                        count, own, scale)
    got = kv_all[layer, jnp.arange(B)[:, None], rows]          # [B, K, 2 kvh, hd]
    k, v = got[:, :, :kvh], got[:, :, kvh:]
    s = jnp.einsum("bgrd,bkgd->bgrk", q, k, preferred_element_type=F32) * scale
    s = jnp.where((jnp.arange(rows.shape[1])[None, :] < count[:, None]
                   )[:, None, None], s, -1e30)
    s_own = jnp.sum(q.astype(F32) * k_cur.astype(F32)[:, :, None], axis=-1) * scale
    s_own = jnp.where(own[:, None, None], s_own, -1e30)
    m = jnp.maximum(jnp.max(s, axis=-1), s_own)
    p, p_own = jnp.exp(s - m[..., None]), jnp.exp(s_own - m)
    acc = jnp.einsum("bgrk,bkgd->bgrd", p.astype(v.dtype), v,
                     preferred_element_type=F32)
    acc = acc + p_own[..., None] * v_cur.astype(F32)[:, :, None]
    return (acc / (jnp.sum(p, axis=-1) + p_own)[..., None]).astype(q.dtype)


def write_positions(kv_all, rows, lengths):
    """The decode step's write of a position's block: rows [L, B, 2 kvh, hd]
    go to position `lengths[b]` of slot b in every layer of kv_all
    [L, B, max_len, 2 kvh, hd]. A slot of length 0 holds nothing and writes
    nothing; one whose position is at or past max_len neither. A position
    is whole tiles, so each slot's update stays in place in the donated
    cache (`ops.cache.write_rows` says what a window of one ROW costs)."""
    L, B, max_len = kv_all.shape[:3]

    def one(b, kv):
        at = (0, b, jnp.minimum(lengths[b], max_len - 1), 0, 0)
        new = jax.lax.dynamic_slice(rows, (0, b, 0, 0), (L, 1) + rows.shape[2:])
        old = jax.lax.dynamic_slice(kv, at, (L, 1, 1) + kv.shape[3:])
        writes = (lengths[b] > 0) & (lengths[b] < max_len)
        return jax.lax.dynamic_update_slice(
            kv, jnp.where(writes, new[:, :, None], old), at)

    return jax.lax.fori_loop(0, B, one, kv_all)
