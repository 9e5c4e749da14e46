"""Kernels of learned sparse attention (`ops/dsa.py` says what they compute).

The prompt pass, two calls a layer, neither holds an [n, n] score in HBM:

`dsa_select`     grid (query blocks, key blocks): every step scores a
                 [128, 512] tile of indexer scores on the MXU and keeps it,
                 as int32 keys whose signed order is the scores', in a VMEM
                 scratch that holds the block's whole causal score row; the
                 block's last step finds each query's `topk`-th largest key
                 by a radix search (32 passes of compare-and-count over the
                 scratch, the most significant bit first) and, only where
                 keys EQUAL to it make the count overshoot, the highest
                 index among them that still belongs (ties go to the lower
                 index, `lax.top_k`'s order). Out: a threshold and a tie
                 index a query. Bound by the vector unit: 32 passes x n / 2
                 compares a query.
`dsa_attention`  flash attention, grid (query blocks, key blocks), under the
                 mask "key above the threshold, or equal to it and no later
                 than the tie index": the tile's indexer keys are computed
                 again by the same code (bit for bit: the MXU's sums over 64
                 lanes do not depend on the tile), 1/8 of the attention's
                 own products. A query block's four kv heads are rows
                 [rep x 256, 128] each (the group's queries stacked), so
                 the mask is tiled, not gathered. Optionally writes the
                 chosen rows, 32 queries a word (`ops.dsa.pack_rows`).

The decode step, two calls a layer, no grid (`decode_attention.py`'s form):

`dsa_scores`     loops over the busy slots' blocks of 512 live indexer keys,
                 each fetched by its own DMA while the last is multiplied:
                 scores [B, attn_len] float32, -inf past a slot's length.
`dsa_rows`       attention over a row LIST: for each busy slot, 128 listed
                 rows at a time, one DMA a row (`[k ; v]` [8, 128]: one tile,
                 one contiguous read a position) into one of two buffers;
                 the online softmax starts from the position's own row where
                 that row was chosen.

Where the two decode kernels earn their place (TPU v5e, the step alone, 8
slots x 32768, six layers; PERF.md, PR 56): at 3 busy slots of 9-16k
positions the step takes 5.94 ms with both, 6.16 with XLA's gather in
`dsa_rows`' place and 6.11 with XLA's masked scores over the whole window in
`dsa_scores`' place: a kernel skips the idle slots and the dead rows, which
XLA's forms read. At 8 busy slots of 8-30k the gather WINS (10.40 against
11.96 ms: `dsa_rows` pays ~29 ns a row's DMA, not bytes) and `dsa_scores`
saves nothing. So they are for a replica that runs below its knee, a few of
its slots busy; they are gated on shapes alone (`uses_scores_kernel`,
`uses_rows_kernel`), because the busy count is not known when the step is
traced, and a list of BLOCKS is what would win at every load (ROADMAP R7, a).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.pallas import _util
from ray_tpu.ops.pallas.decode_attention import live_items as _live_items

F32 = jnp.float32
_NEG = -1e30
_INT_MIN = -2 ** 31
QUERY_BLOCK = 256        # queries a step of `dsa_attention`
SELECT_BLOCK = 128       # queries a step of `dsa_select`
KEY_BLOCK = 512          # keys a step of either
_VMEM_LIMIT = 100 * 2 ** 20
SCORE_ROWS = 512         # live indexer keys an item of `dsa_scores` fetches
LIST_ROWS = 128          # listed rows a round of `dsa_rows` fetches
_LIST_BYTES = 256 * 2 ** 10   # the most scalar memory the row lists may take


# ------------------------------------------------------------ prompt pass


def _score_keys(qi_ref, wi_ref, ki, row0, col0, J: int, di: int):
    """The tile's indexer scores as sortable keys: qi_ref [1, J, bq, di],
    wi_ref [1, J, bq, 1] float32, ki [bk, W] -> [bq, bk] int32 whose SIGNED
    order is the float32 scores' (INT_MIN where the column is no causal row
    of the query). One head after another, in order: both kernels get the
    same sums."""
    k = ki[:, :di]
    acc = None
    for j in range(J):
        s = jax.lax.dot_general(qi_ref[0, j], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)
        term = wi_ref[0, j] * jnp.maximum(s, 0.0)
        acc = term if acc is None else acc + term
    # (-0.0 and 0.0 are one score: without this they would be two keys)
    bits = jax.lax.bitcast_convert_type(jnp.where(acc == 0.0, 0.0, acc), jnp.int32)
    keys = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
    return jnp.where(cols <= rows, keys, jnp.int32(_INT_MIN))


def _select_kernel(qi_ref, wi_ref, ki_ref, thr_ref, tie_ref, keys_scr, *,
                   bq: int, bk: int, J: int, di: int, topk: int, nk: int,
                   index_bits: int):
    qb, kb = pl.program_id(0), pl.program_id(1)
    last = ((qb + 1) * bq - 1) // bk       # the last block with a causal row

    @pl.when(kb <= last)
    def _score():
        keys_scr[kb] = _score_keys(qi_ref, wi_ref, ki_ref[...], qb * bq, kb * bk,
                                   J, di)

    def count(test):
        """[bq, 1]: how many keys of each query's causal row pass `test`."""
        def chunk(c, acc):
            return acc + test(keys_scr[c], c * bk).astype(jnp.int32)
        acc = jax.lax.fori_loop(0, last + 1, chunk, jnp.zeros((bq, bk), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    @pl.when(kb == nk - 1)
    def _select():
        # the k-th largest key, bit by bit from the top, in the order of
        # `key ^ INT_MIN` read as unsigned (which is the keys' signed order)
        def bit(i, cand):
            trial = cand | jnp.left_shift(jnp.int32(1), 31 - i)
            signed = trial ^ jnp.int32(_INT_MIN)
            n = count(lambda keys, _: keys >= signed)
            return jnp.where(n >= topk, trial, cand)

        thr = jax.lax.fori_loop(0, 32, bit, jnp.zeros((bq, 1), jnp.int32)) \
            ^ jnp.int32(_INT_MIN)
        thr_ref[...] = thr
        tie_ref[...] = jnp.full((bq, 1), nk * bk, jnp.int32)
        # (a query of fewer than topk causal rows has INT_MIN: every row)
        over = (count(lambda keys, _: keys >= thr) > topk) & (thr != _INT_MIN)

        @pl.when(jnp.max(over.astype(jnp.int32)) > 0)
        def _ties():
            # keys equal to the threshold overshoot: of those, the first
            # `need` by index belong. The largest c with fewer than `need`
            # equal keys before column c is that last index
            need = topk - count(lambda keys, _: keys > thr)

            def bit(i, cand):
                trial = cand | jnp.left_shift(jnp.int32(1), index_bits - 1 - i)
                cols = lambda c0: c0 + jax.lax.broadcasted_iota(
                    jnp.int32, (bq, bk), 1)
                n = count(lambda keys, c0: (keys == thr) & (cols(c0) < trial))
                return jnp.where(n < need, trial, cand)

            tie = jax.lax.fori_loop(0, index_bits, bit,
                                    jnp.zeros((bq, 1), jnp.int32))
            tie_ref[...] = jnp.where(over, tie, nk * bk)


def _attention_kernel(q_ref, k_ref, v_ref, qi_ref, wi_ref, ki_ref, thr_ref,
                      tie_ref, o_ref, *rest, bq: int, bk: int, J: int, di: int,
                      kvh: int, rep: int, nk: int, scale: float, with_rows: bool):
    rows_ref = rest[0] if with_rows else None
    m_ref, l_ref, acc_ref = rest[-3:]
    qb, kb = pl.program_id(0), pl.program_id(1)
    last = ((qb + 1) * bq - 1) // bk

    @pl.when(kb == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kb <= last)
    def _block():
        keys = _score_keys(qi_ref, wi_ref, ki_ref[...], qb * bq, kb * bk, J, di)
        rows = qb * bq + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 0)
        cols = kb * bk + jax.lax.broadcasted_iota(jnp.int32, keys.shape, 1)
        thr, tie = thr_ref[...], tie_ref[...]
        chosen = ((keys > thr) | ((keys == thr) & (cols <= tie))) & (cols <= rows)
        if with_rows:
            bits = chosen.astype(jnp.int32).reshape(bq // 32, 32, bk)
            shift = jax.lax.broadcasted_iota(jnp.int32, bits.shape, 1)
            rows_ref[...] = jnp.sum(jnp.left_shift(bits, shift), axis=1)
        # the group's `rep` query heads are stacked head-major: rows
        # [r x bq, (r + 1) x bq) are head r of the block's queries
        mask = jnp.concatenate([chosen.astype(jnp.int32)] * rep, axis=0) > 0
        for g in range(kvh):
            s = jax.lax.dot_general(q_ref[g, 0], k_ref[g], (((1,), (1,)), ((), ())),
                                    preferred_element_type=F32) * scale
            s = jnp.where(mask, s, _NEG)
            m_prev = m_ref[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[g] = acc_ref[g] * alpha + jnp.dot(
                p.astype(v_ref.dtype), v_ref[g], preferred_element_type=F32)
            m_ref[g] = m_new

    @pl.when(kb == nk - 1)
    def _close():
        # a padded query may have chosen nothing it can see: l = 0 there
        o_ref[:, 0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)


def _blocked(a, bq: int):
    """[n, G, w] -> [n / bq, G, bq, w]: a block's rows head-major."""
    n, G, w = a.shape
    return jnp.moveaxis(a.reshape(n // bq, bq, G, w), 2, 1)


def select(qi, wi, ki, topk: int):
    """qi [n, J, di], wi [n, J] float32, ki [n, W] -> (thr [n, 1] int32:
    each query's `topk`-th largest causal score as a sortable key (INT_MIN:
    every row), tie [n, 1] int32: of the rows whose key EQUALS it, those up
    to this index belong)."""
    n, J, di = qi.shape
    bq, bk = SELECT_BLOCK, KEY_BLOCK
    nq, nk = n // bq, n // bk
    causal = lambda qb, kb: jnp.minimum(kb, ((qb + 1) * bq - 1) // bk)
    kernel = functools.partial(
        _select_kernel, bq=bq, bk=bk, J=J, di=di, topk=topk, nk=nk,
        index_bits=max(1, (n - 1).bit_length()))
    per_query = pl.BlockSpec((bq, 1), lambda qb, kb: (qb, 0))
    return pl.pallas_call(
        kernel,
        grid=(nq, nk),
        in_specs=[pl.BlockSpec((1, J, bq, di), lambda qb, kb: (qb, 0, 0, 0)),
                  pl.BlockSpec((1, J, bq, 1), lambda qb, kb: (qb, 0, 0, 0)),
                  pl.BlockSpec((bk, ki.shape[1]), lambda qb, kb: (causal(qb, kb), 0))],
        out_specs=[per_query, per_query],
        out_shape=[jax.ShapeDtypeStruct((n, 1), jnp.int32)] * 2,
        scratch_shapes=[pltpu.VMEM((nk, bq, bk), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="dsa_select",
        interpret=_util.interpret_mode(),
    )(_blocked(qi, bq), _blocked(wi[..., None], bq), ki)


def attention(q, k, v, qi, wi, ki, thr, tie, scale: float, with_rows: bool):
    """Flash attention of one prompt under `select`'s choice: q [n, H, hd],
    k, v [n, kvh, hd], qi, wi, ki as `select` -> (o [n, H, hd], None or the
    chosen rows packed [n / 32, n] int32)."""
    n, H, hd = q.shape
    kvh = k.shape[1]
    rep, (J, di) = H // kvh, qi.shape[1:]
    bq, bk = QUERY_BLOCK, KEY_BLOCK
    nq, nk = n // bq, n // bk
    causal = lambda qb, kb: jnp.minimum(kb, ((qb + 1) * bq - 1) // bk)
    # [kvh, nq, rep x bq, hd]: a kv head's query heads stacked head-major
    q5 = jnp.transpose(q.reshape(nq, bq, kvh, rep, hd), (2, 0, 3, 1, 4)
                       ).reshape(kvh, nq, rep * bq, hd)
    stacked = pl.BlockSpec((kvh, 1, rep * bq, hd), lambda qb, kb: (0, qb, 0, 0))
    keys = pl.BlockSpec((kvh, bk, hd), lambda qb, kb: (0, causal(qb, kb), 0))
    per_query = pl.BlockSpec((bq, 1), lambda qb, kb: (qb, 0))
    out_specs, out_shape = [stacked], [jax.ShapeDtypeStruct(q5.shape, q.dtype)]
    if with_rows:
        out_specs.append(pl.BlockSpec((bq // 32, bk), lambda qb, kb: (qb, kb)))
        out_shape.append(jax.ShapeDtypeStruct((n // 32, n), jnp.int32))
    kernel = functools.partial(
        _attention_kernel, bq=bq, bk=bk, J=J, di=di, kvh=kvh, rep=rep, nk=nk,
        scale=scale, with_rows=with_rows)
    out = pl.pallas_call(
        kernel,
        grid=(nq, nk),
        in_specs=[stacked, keys, keys,
                  pl.BlockSpec((1, J, bq, di), lambda qb, kb: (qb, 0, 0, 0)),
                  pl.BlockSpec((1, J, bq, 1), lambda qb, kb: (qb, 0, 0, 0)),
                  pl.BlockSpec((bk, ki.shape[1]), lambda qb, kb: (causal(qb, kb), 0)),
                  per_query, per_query],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((kvh, rep * bq, 1), F32),
                        pltpu.VMEM((kvh, rep * bq, 1), F32),
                        pltpu.VMEM((kvh, rep * bq, hd), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="dsa_attention",
        interpret=_util.interpret_mode(),
    )(q5, jnp.moveaxis(k, 0, 1), jnp.moveaxis(v, 0, 1), _blocked(qi, bq),
      _blocked(wi[..., None], bq), ki, thr, tie)
    if with_rows:
        # kept apart from what takes the words: fused with the layers' scan
        # writing them down, the call would be held to XLA's own 16 MB of VMEM
        out = jax.lax.optimization_barrier(out)
    o = jnp.transpose(out[0].reshape(kvh, nq, rep, bq, hd), (1, 3, 0, 2, 4))
    return o.reshape(n, H, hd), (out[1] if with_rows else None)


def prompt_attention(q, k, v, qi, wi, ki, *, topk: int, scale: float,
                     with_rows: bool):
    """`ops.dsa.prompt_attention` for one prompt, by the two kernels."""
    with jax.named_scope("select"):
        thr, tie = select(qi, wi, ki, topk)
    with jax.named_scope("attend"):
        return attention(q, k, v, qi, wi, ki, thr, tie, scale, with_rows)


# ------------------------------------------------------------ decode step


def uses_scores_kernel(ik_all: jax.Array, attn_len: int) -> bool:
    """Whether the decode step runs `dsa_scores` over the indexer keys
    [L, B, 1, max_len, W] for a window of `attn_len` rows: on a TPU, whole
    lanes and whole blocks."""
    return (_util.on_tpu() and ik_all.shape[-1] % 128 == 0
            and attn_len % SCORE_ROWS == 0)


def uses_rows_kernel(kv_all: jax.Array, listed: int) -> bool:
    """Whether the decode step runs `dsa_rows` over kv_all
    [L, B, max_len, 2 kvh, hd] for lists of `listed` rows a slot: on a TPU,
    where a position is whole (8, 128) tiles of its type (what a DMA can
    name) and the lists fit the scalar memory."""
    return (_util.on_tpu() and kv_all.shape[-1] % 128 == 0
            and kv_all.shape[-2] % (32 // kv_all.dtype.itemsize // 2) == 0
            and listed % LIST_ROWS == 0
            and kv_all.shape[1] * listed * 4 <= _LIST_BYTES)


def live_items(lengths: jax.Array, attn_len: int):
    """`decode_attention.live_items` at this kernel's block height."""
    return _live_items(lengths, attn_len, SCORE_ROWS)


def _scores_kernel(layer_ref, slot_ref, block_ref, count_ref, held_ref,
                   qi_ref, wi_ref, ik_hbm, o_ref, buf, sem, *, J: int, di: int):
    layer, count = layer_ref[0], count_ref[0]
    R = SCORE_ROWS

    def copy(item, b):
        at = (layer, slot_ref[item], 0,
              pl.ds(pl.multiple_of(block_ref[item] * R, R), R))
        return pltpu.make_async_copy(ik_hbm.at[at], buf.at[b], sem.at[b])

    pl.when(count > 0)(lambda: copy(0, 0).start())
    o_ref[...] = jnp.full(o_ref.shape, -jnp.inf, F32)

    def walk(item, _):
        b = item % 2
        pl.when(item + 1 < count)(lambda: copy(item + 1, 1 - b).start())
        slot, j = slot_ref[item], block_ref[item]
        copy(item, b).wait()
        s = jax.lax.dot_general(qi_ref[slot], buf[b][:, :di],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=F32)            # [J, R]
        score = jnp.sum(wi_ref[slot] * jnp.maximum(s, 0.0), axis=0, keepdims=True)
        cols = j * R + jax.lax.broadcasted_iota(jnp.int32, score.shape, 1)
        o_ref[slot, :, pl.ds(pl.multiple_of(j * R, R), R)] = jnp.where(
            cols < held_ref[slot], score, -jnp.inf)

    jax.lax.fori_loop(0, count, walk, None)


def decode_scores(qi, wi, ik_all, layer, lengths, attn_len: int):
    """qi [B, J, di], wi [B, J] float32, ik_all [L, B, 1, max_len, W] ->
    scores [B, attn_len] float32 of slot b's rows [0, lengths[b]), -inf
    behind them: only the blocks that hold a live key are read."""
    B, J, di = qi.shape
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        functools.partial(_scores_kernel, J=J, di=di),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5, grid=(),
            in_specs=[whole, whole, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[pltpu.VMEM((2, SCORE_ROWS, ik_all.shape[-1]),
                                       ik_all.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        out_shape=jax.ShapeDtypeStruct((B, 1, attn_len), F32),
        name="dsa_scores",
        interpret=_util.interpret_mode(),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *live_items(lengths, attn_len),
      qi, wi[..., None], ik_all)[:, 0]


def _each(n: int, body, unroll: int = 8):
    """body(i) for i in [0, n), `unroll` calls an iteration of the loop."""
    unroll = unroll if n % unroll == 0 else 1

    def some(i, _):
        for u in range(unroll):
            body(i * unroll + u)
    jax.lax.fori_loop(0, n // unroll, some, None)


def _rows_kernel(layer_ref, rows_ref, count_ref, own_ref,       # scalars
                 q_ref, kc_ref, vc_ref, kv_hbm, o_ref,
                 buf, sem, acc_ref, m_ref, l_ref, *,
                 B: int, kvh: int, hd: int, scale: float):
    layer = layer_ref[0]
    R = LIST_ROWS

    def fetch(slot, r, b):
        """Round r of the slot's list: R rows, one DMA each, into buffer b;
        entries past the count fetch the list's first row again."""
        n = count_ref[slot]

        def one(i):
            at = jnp.where(r * R + i < n, r * R + i, 0)
            pltpu.make_async_copy(kv_hbm.at[layer, slot, rows_ref[slot, at]],
                                  buf.at[b, i], sem.at[b]).start()
        _each(R, one)

    def wait(b):
        _each(R, lambda i: pltpu.make_async_copy(
            kv_hbm.at[layer, 0, 0], buf.at[b, i], sem.at[b]).wait())

    def slot_loop(slot, _):
        n = count_ref[slot]
        rounds = (n + R - 1) // R
        q = q_ref[slot]                                   # [kvh, rep, hd]
        # the position's own row opens the softmax where it was chosen
        s = jnp.sum(q.astype(F32) * kc_ref[slot][:, None], axis=-1,
                    keepdims=True) * scale
        own = own_ref[slot] > 0
        m_ref[...] = jnp.where(own, s, _NEG)
        l_ref[...] = jnp.where(own, jnp.ones_like(s), 0.0)
        acc_ref[...] = jnp.where(own, 1.0, 0.0) * jnp.broadcast_to(
            vc_ref[slot][:, None], acc_ref.shape)
        pl.when(rounds > 0)(lambda: fetch(slot, 0, 0))

        def round_(r, _):
            b = r % 2
            pl.when(r + 1 < rounds)(lambda: fetch(slot, r + 1, 1 - b))
            wait(b)
            got = buf[b]                                   # [R, 2 kvh, hd]
            live = r * R + jax.lax.broadcasted_iota(jnp.int32, (1, R), 1) < n
            for g in range(kvh):
                k, v = got[:, g], got[:, kvh + g]
                sc = jax.lax.dot_general(q[g], k, (((1,), (1,)), ((), ())),
                                         preferred_element_type=F32) * scale
                sc = jnp.where(live, sc, _NEG)
                m_prev = m_ref[g]
                m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
                p = jnp.where(live, jnp.exp(sc - m_new), 0.0)
                alpha = jnp.exp(m_prev - m_new)
                l_ref[g] = l_ref[g] * alpha + jnp.sum(p, axis=-1, keepdims=True)
                acc_ref[g] = acc_ref[g] * alpha + jnp.dot(
                    p.astype(got.dtype), v, preferred_element_type=F32)
                m_ref[g] = m_new

        jax.lax.fori_loop(0, rounds, round_, None)
        o_ref[slot] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
                       ).astype(o_ref.dtype)

    jax.lax.fori_loop(0, B, slot_loop, None)


def decode_attention(q, k_cur, v_cur, kv_all, layer, rows, count, own,
                     scale: float):
    """`ops.dsa.decode_attention` by the kernel: the listed rows alone are
    read, a DMA each."""
    B, kvh, rep, hd = q.shape
    rep_pad = _util.round_up(rep, 8)
    q = jnp.pad(q, ((0, 0), (0, 0), (0, rep_pad - rep), (0, 0)))
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_rows_kernel, B=B, kvh=kvh, hd=hd, scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=(),
            in_specs=[whole, whole, whole, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=whole,
            scratch_shapes=[pltpu.VMEM((2, LIST_ROWS) + kv_all.shape[3:], kv_all.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.VMEM((kvh, rep_pad, hd), F32),
                            pltpu.VMEM((kvh, rep_pad, 1), F32),
                            pltpu.VMEM((kvh, rep_pad, 1), F32)]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        name="dsa_rows",
        interpret=_util.interpret_mode(),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), rows, count,
      own.astype(jnp.int32), q, k_cur.astype(F32), v_cur.astype(F32), kv_all)
    return out[:, :, :rep]
