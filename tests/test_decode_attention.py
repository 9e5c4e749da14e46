"""The length-aware decode attention kernel (interpret mode on the CPU, its
copies and semaphores the interpreter's) against `_gqa_decode_attention`,
the CPU path and the kernel's reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.inference import _gqa_decode_attention
from ray_tpu.ops.pallas import _util, decode_attention

L, KVH, HD, MAX_LEN, ROWS = 3, 2, 128, 128, 32
LAYER = 1


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of 32 rows, so that a toy window has several of them."""
    monkeypatch.setattr(decode_attention, "_BLOCK_ROWS", ROWS)


def _inputs(lengths, rep, dtype, seed=0, KVH=KVH):
    B = len(lengths)
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda k, dims: jax.random.normal(k, dims, jnp.float32).astype(dtype)
    return (normal(ks[0], (B, KVH, rep, HD)), normal(ks[1], (B, KVH, HD)),
            normal(ks[2], (B, KVH, HD)), normal(ks[3], (L, B, KVH, MAX_LEN, HD)),
            normal(ks[4], (L, B, KVH, MAX_LEN, HD)))


def _kernel(q, k_cur, v_cur, k_all, v_all, lengths, attn_len):
    lengths = jnp.asarray(lengths, jnp.int32)
    return decode_attention.gqa_decode_attention(
        q, k_cur, v_cur, k_all, v_all, jnp.asarray(LAYER),
        decode_attention.live_items(lengths, attn_len), attn_len)


def _reference(q, k_cur, v_cur, k_all, v_all, lengths, attn_len):
    B, kvh, rep, hd = q.shape
    mask = jnp.arange(attn_len)[None] < jnp.asarray(lengths)[:, None]
    out = _gqa_decode_attention(
        q.reshape(B, kvh * rep, 1, hd), k_all[LAYER, :, :, :attn_len],
        v_all[LAYER, :, :, :attn_len], k_cur, v_cur, mask)
    return out.reshape(B, kvh, rep, hd)


def _plant_nan(cache, lengths):
    """NaN in every row at and past a slot's length, in every layer."""
    dead = jnp.arange(MAX_LEN)[None] >= jnp.asarray(lengths)[:, None]
    return jnp.where(dead[None, :, None, :, None], jnp.nan, cache)


# 0 (idle), 1, a block edge and one past it, the window, and max_len (a
# slot deeper than the window, as a just-retired one can be for a step)
RAGGED = [0, 1, ROWS, ROWS + 1, 64, MAX_LEN]


@pytest.mark.parametrize("attn_len", [64, MAX_LEN])
@pytest.mark.parametrize("rep", [1, 2, 4])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)])
def test_kernel_matches_the_einsums_on_ragged_lengths(dtype, tol, rep, attn_len):
    args = _inputs(RAGGED, rep, dtype)
    got = _kernel(*args, RAGGED, attn_len)
    want = _reference(*args, RAGGED, attn_len)
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("rep", [1, 2, 4])
def test_rows_past_a_length_and_idle_slots_do_not_reach_the_result(rep):
    """NaN planted at and past `lengths[b]` (all of an idle slot): the
    result is finite and bit for bit that of the clean cache, so those rows
    are not read into the answer."""
    q, k_cur, v_cur, k_all, v_all = _inputs(RAGGED, rep, jnp.float32, seed=1)
    clean = _kernel(q, k_cur, v_cur, k_all, v_all, RAGGED, MAX_LEN)
    dirty = _kernel(q, k_cur, v_cur, _plant_nan(k_all, RAGGED),
                    _plant_nan(v_all, RAGGED), RAGGED, MAX_LEN)
    assert np.isfinite(np.asarray(dirty)).all()
    np.testing.assert_array_equal(np.asarray(dirty), np.asarray(clean))


@pytest.mark.parametrize("rep", [1, 2, 4])
def test_an_idle_slot_gives_its_self_term(rep):
    lengths = [0, 40, 0, 0]
    q, k_cur, v_cur, k_all, v_all = _inputs(lengths, rep, jnp.bfloat16, seed=2)
    out = _kernel(q, k_cur, v_cur, _plant_nan(k_all, lengths),
                  _plant_nan(v_all, lengths), lengths, 64)
    for b in (0, 2, 3):  # softmax over the one self score is 1: v_cur itself
        np.testing.assert_array_equal(
            np.asarray(out[b], np.float32),
            np.broadcast_to(np.asarray(v_cur[b], np.float32)[:, None], out[b].shape))


# the dense step's heads (InternLM2: 8 kv heads x 2 queries each) and the runs
# form's (Jamba: ONE kv head x 20, padded to 24 sublanes); a whole tile of
# queries a kv head, and a ratio that divides no tile
HEADS = [(8, 2), (1, 20), (2, 8), (2, 3)]
BATCHES = {
    "ragged": [0, 1, ROWS - 1, ROWS, ROWS + 1, MAX_LEN],
    "nothing_busy": [0, 0, 0, 0],
    "everything_busy_and_full": [MAX_LEN] * 4,
    "idle_between_busy": [0, 40, 0, 0, ROWS + 1, 0],
}


@pytest.mark.parametrize("kvh,rep", HEADS)
@pytest.mark.parametrize("batch", list(BATCHES))
def test_the_walk_at_both_callers_heads(batch, kvh, rep):
    """One call walks every (slot, block) item that holds a row: against the
    einsums, with NaN planted in every row at and past a length (an idle
    slot's whole window), so a row that must not be read shows; an idle
    slot's output is its self term, whichever slots around it are busy."""
    lengths = BATCHES[batch]
    q, k_cur, v_cur, k_all, v_all = _inputs(lengths, rep, jnp.bfloat16,
                                            seed=3, KVH=kvh)
    got = _kernel(q, k_cur, v_cur, _plant_nan(k_all, lengths),
                  _plant_nan(v_all, lengths), lengths, MAX_LEN)
    want = _reference(q, k_cur, v_cur, k_all, v_all, lengths, MAX_LEN)
    assert got.shape == want.shape == (len(lengths), kvh, rep, HD)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)
    for b, n in enumerate(lengths):
        if n == 0:
            np.testing.assert_array_equal(
                np.asarray(got[b], np.float32),
                np.broadcast_to(np.asarray(v_cur[b], np.float32)[:, None],
                                got[b].shape))


@pytest.mark.parametrize("lengths,attn_len,rows,want", [
    # exactly the blocks that hold rows: 3 of slot 2, 1 of slot 4, 4 of slot 5
    ([0, 0, 70, 0, 1, MAX_LEN, 0], MAX_LEN, 0,
     [(2, 0), (2, 1), (2, 2), (4, 0), (5, 0), (5, 1), (5, 2), (5, 3)]),
    ([0, 0, 0, 0], MAX_LEN, 0, []),
    ([MAX_LEN] * 3, MAX_LEN, 0, [(b, j) for b in range(3) for j in range(4)]),
    # a slot deeper than the window walks the window's blocks alone
    ([MAX_LEN, 0, ROWS], 64, 0, [(0, 0), (0, 1), (2, 0)]),
    # a block edge: `rows` rows are one block, one more row is two
    ([ROWS - 1, ROWS, ROWS + 1], MAX_LEN, 0, [(0, 0), (1, 0), (2, 0), (2, 1)]),
    # another block height
    ([70, 0, 64], MAX_LEN, 64, [(0, 0), (0, 1), (2, 0)]),
])
def test_live_items_list_the_blocks_that_hold_rows(lengths, attn_len, rows, want):
    """Slots ascending, a slot's blocks ascending, an idle slot nowhere; the
    count; and each slot's rows inside the window."""
    slot, block, count, held = (np.asarray(a) for a in decode_attention.live_items(
        jnp.asarray(lengths, jnp.int32), attn_len, rows))
    n_max = len(lengths) * (attn_len // (rows or ROWS))
    assert slot.shape == block.shape == (n_max,) and count.shape == (1,)
    assert count[0] == len(want)
    assert list(zip(slot[:count[0]].tolist(), block[:count[0]].tolist())) == want
    assert held.tolist() == [min(n, attn_len) for n in lengths]
    # what lies past the count is never read, and is a valid index all the same
    assert ((0 <= slot) & (slot < len(lengths))).all() and (block[count[0]:] == 0).all()


def test_live_blocks_repeat_what_is_already_fetched():
    """Busy slots first; each walks blocks 0..its last; past that, and for
    the idle slots behind them, the index is the one fetched last, so no DMA
    is issued."""
    lengths = jnp.asarray([0, 0, 70, 0, 1, MAX_LEN, 0], jnp.int32)
    order, rows, src, lo, hi = (np.asarray(a) for a in
                                decode_attention.live_blocks(lengths, MAX_LEN))
    assert order.tolist() == [2, 4, 5, 0, 1, 3, 6]
    assert rows.tolist() == [70, 1, MAX_LEN, 0, 0, 0, 0]
    fetched = [(int(src[i]), int(np.clip(j, lo[i], hi[i])))
               for i in range(len(order)) for j in range(MAX_LEN // ROWS)]
    distinct = [f for n, f in enumerate(fetched) if n == 0 or f != fetched[n - 1]]
    # exactly the blocks that hold rows: 3 of slot 2, 1 of slot 4, 4 of slot 5
    assert distinct == [(2, 0), (2, 1), (2, 2), (4, 0),
                        (5, 0), (5, 1), (5, 2), (5, 3)]
    # nothing busy: one block (of slot 0), fetched once
    _, _, src, lo, hi = (np.asarray(a) for a in decode_attention.live_blocks(
        jnp.zeros((4,), jnp.int32), MAX_LEN))
    assert src.tolist() == lo.tolist() == hi.tolist() == [0, 0, 0, 0]


def test_the_kernel_is_chosen_by_what_the_code_can_see(monkeypatch):
    cache = jax.ShapeDtypeStruct((2, 4, 2, 1024, 128), jnp.bfloat16)
    assert not decode_attention.uses_decode_kernel(cache, 64)  # CPU here
    monkeypatch.setattr(_util, "on_tpu", lambda: True)
    monkeypatch.setattr(decode_attention, "_BLOCK_ROWS", 256)
    assert decode_attention.uses_decode_kernel(cache, 64)
    assert decode_attention.uses_decode_kernel(cache, 1024)
    assert not decode_attention.uses_decode_kernel(cache, 520)  # no whole blocks
    assert not decode_attention.uses_decode_kernel(
        jax.ShapeDtypeStruct((2, 4, 2, 1024, 64), jnp.bfloat16), 64)  # half a lane tile
