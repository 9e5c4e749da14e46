"""EVA attention (`ops/eva.py`), its decode kernel in interpret mode, the
`eva` mixer of the runs form in its three call modes, its slot state
(`EvaCache`) under the engine, and the two widenings of the engine's cache
interface, at a small size: three layers, d 64, 4 heads, windows of 32
positions in chunks of 4, two prediction heads. The yardstick is the
benchmark's plain float32 reference, `perfbench/references/evabyte.py`."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench.lib.manifest import load_py  # noqa: E402
from ray_tpu.models import hybrid  # noqa: E402
from ray_tpu.models.hybrid import HybridConfig  # noqa: E402
from ray_tpu.models.serving import (ContinuousBatchingEngine, DenseKVCache,  # noqa: E402
                                    _bucket_len)
from ray_tpu.models.transformer import ModelConfig  # noqa: E402
from ray_tpu.ops import eva  # noqa: E402
from ray_tpu.ops.attention import causal_attention_blocked  # noqa: E402
from ray_tpu.ops.cache import write_rows  # noqa: E402
from ray_tpu.ops.pallas import eva_decode  # noqa: E402

CFG = HybridConfig.tiny_eva()
W, C = CFG.eva_window, CFG.eva_chunk
# the reference reads a configuration FILE's keys
FILE = {"num_attention_heads": 4, "hidden_size": 64, "window_size": W,
        "chunk_size": C, "rope_theta": 1e5, "rms_norm_eps": 1e-5,
        "num_pred_heads": 2, "vocab_size": 64}


@pytest.fixture(scope="module")
def ref():
    return load_py(os.path.join(ROOT, "perfbench", "references", "evabyte.py"))


@pytest.fixture(scope="module")
def params():
    return hybrid.init_params(jax.random.PRNGKey(7), CFG)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(8), (2, 101), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def full(params, tokens):
    return hybrid.forward(params, tokens, CFG)


# ------------------------------------------------------------------ forward

@pytest.mark.parametrize("s", [7, 32, 45, 101])
def test_forward_is_the_reference_in_every_head(ref, params, tokens, s):
    """Under a window, one window, an open window behind a closed one, and
    three closed windows with a chunk left open."""
    got = hybrid.forward(params, tokens[:, :s], CFG, all_heads=True)
    want = ref.logits(params, tokens[:, :s], FILE)
    assert got.shape == want.shape == (2, s, 2, 64)
    assert float(ref.rel_err(got, want)) < 2e-5
    np.testing.assert_array_equal(got[:, :, 0], hybrid.forward(params, tokens[:, :s], CFG))


def test_a_wide_window_is_plain_causal_attention():
    """W >= the sequence: no summary is ever seen, and the mixer is
    `causal_attention_blocked` on the same q, k, v."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(a, (2, 48, 4, 16)) for a in ks)
    none = jnp.zeros((2, 4, 0, 16))
    got = eva.window_attention(q, k, v, none, none, 0, 0.25, q_block=16)
    want = causal_attention_blocked(q, k, v, sm_scale=0.25, q_block=16)
    np.testing.assert_allclose(got, want, atol=2e-6)
    wide = dataclasses.replace(CFG, eva_window=64)
    p = hybrid.init_params(jax.random.PRNGKey(7), wide)
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 50), 0, 64)
    # ... and then neither `phi` nor `mu` can matter
    p2 = jax.tree_util.tree_map(lambda a: a, p)
    p2["runs"][0]["eva"]["phi"] = jnp.zeros_like(p["runs"][0]["eva"]["phi"])
    np.testing.assert_array_equal(hybrid.forward(p, toks, wide),
                                  hybrid.forward(p2, toks, wide))


@pytest.mark.parametrize("leaf", ["phi", "mu"])
def test_dropping_a_summary_vector_changes_the_logits(params, tokens, full, leaf):
    p = jax.tree_util.tree_map(lambda a: a, params)
    p["runs"][0]["eva"][leaf] = jnp.zeros_like(p["runs"][0]["eva"][leaf])
    got = hybrid.forward(p, tokens, CFG)
    # the first window sees no summary; behind it every position does
    np.testing.assert_array_equal(got[:, :W], full[:, :W])
    assert float(jnp.abs(got[:, W:] - full[:, W:]).max()) > 1e-3


def test_dropping_the_unit_offset_changes_the_logits(params, tokens, full):
    got = hybrid.forward(params, tokens, dataclasses.replace(CFG, norm_unit_offset=False))
    assert float(jnp.abs(got - full).max()) > 1e-2


# ------------------------------------------------- prefill, decode, the cache

def _admit(cache, params, prompts, slots, lengths, tokens):
    bucket = max(cache.prompt_bucket(len(p)) for p in prompts)
    rows = np.zeros((len(prompts), bucket), np.int32)
    for i, p in enumerate(prompts):
        rows[i, :len(p)] = p
    lens = jnp.asarray([len(p) for p in prompts], jnp.int32)
    first, state_rows = cache.prefill(params, jnp.asarray(rows), lens)
    return cache.write(lengths, tokens, jnp.asarray(slots, jnp.int32), state_rows,
                       lens, first)


@pytest.fixture
def kernel_path(request, monkeypatch):
    """`True`: the decode step takes the Pallas kernel (interpret mode here),
    in blocks of 8 rows so that the toy table has several."""
    if request.param:
        monkeypatch.setattr(eva_decode, "_BLOCK_ROWS", 8)
        monkeypatch.setattr(eva_decode, "uses_decode_kernel", lambda *a: True)
    jax.clear_caches()
    yield request.param
    jax.clear_caches()


@pytest.mark.parametrize("kernel_path", [False, True], indirect=True)
def test_prefill_and_decode_through_the_cache_are_forward(params, tokens, full,
                                                          kernel_path):
    """Two slots at different phases (prompts of 21 and 15) decoded 70
    positions, teacher-forced: each crosses two window closes and seventeen
    chunk closes, at steps of its own; every position's logits are
    `forward`'s."""
    cache = CFG.make_cache(4, 128)
    lengths, last = jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32)
    at = {2: (0, 21), 0: (1, 15)}            # slot -> (row of `tokens`, prompt)
    lengths, last = _admit(cache, params, [list(tokens[r, :n]) for r, n in at.values()],
                           list(at), lengths, last)
    closed = np.zeros(2, int)
    for _ in range(70):
        n = np.asarray(lengths)
        feed = np.zeros((4,), np.int32)
        for slot, (r, _) in at.items():
            feed[slot] = tokens[r, n[slot]]
        cache.state, logits, _ = hybrid.decode_logits(
            params, cache.state, lengths, jnp.asarray(feed), None, CFG, 128)
        for slot, (r, _) in at.items():
            np.testing.assert_allclose(logits[slot], full[r, n[slot]], atol=5e-6)
        closed += [sum((n[s] + 1) % C == 0 for s in at), sum((n[s] + 1) % W == 0 for s in at)]
        lengths = lengths + (lengths > 0)
    assert list(np.asarray(lengths)) == [85, 0, 91, 0] and list(closed) == [35, 4]


def test_the_step_counts_the_chunks_and_windows_it_closes(params, tokens):
    cache = CFG.make_cache(4, 128)
    lengths, last = jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32)
    # position 31 closes a chunk and the window; 7 a chunk; 9 nothing
    lengths, last = _admit(cache, params, [list(tokens[0, :n]) for n in (31, 7, 9)],
                           [3, 1, 0], lengths, last)
    lengths, _, report = cache.decode(params, lengths, last, 64, {0, 1, 3})
    assert cache.counters == ("chunks_closed", "windows_closed")
    assert list(np.asarray(report[4:])) == [2, 1]
    assert list(np.asarray(lengths)) == [10, 8, 0, 32]


@pytest.mark.parametrize("true_len", [5, 30, 33, 50, 64, 67])
def test_padding_leaves_nothing(params, tokens, full, true_len):
    """A right-padded bucket at a true length that is (mostly) no multiple of
    the chunk or the window: logits and every state row are the same
    whatever lies past it, and however many windows the bucket has."""
    junk = jax.random.randint(jax.random.PRNGKey(3), (1, 96), 0, 64)
    lens = jnp.asarray([true_len], jnp.int32)
    base = None
    for bucket, fill in ((96, junk), (96, jnp.zeros_like(junk)), (-(-true_len // W) * W, junk)):
        toks = fill[:, :bucket].at[:, :true_len].set(tokens[:1, :true_len])
        logits, rows = hybrid.prefill(params, toks, lens, CFG)
        np.testing.assert_allclose(logits[0], full[0, true_len - 1], atol=5e-6)
        n_sum = true_len // C
        assert not np.asarray(rows["sum_k"][:, :, :, n_sum:]).any()
        assert not np.asarray(rows["win_v"][:, :, :, true_len % W:]).any()
        assert not np.asarray(rows["ck"][:, :, :, true_len % C:]).any()
        got = {k: np.asarray(v)[:, :, :, :n_sum] if k.startswith("sum") else np.asarray(v)
               for k, v in rows.items()}
        if base is not None:
            for k in got:
                np.testing.assert_allclose(got[k], base[k], atol=2e-6, err_msg=k)
        base = got
    if true_len % W:   # the open window's rows ARE the keys at its positions
        assert np.asarray(base["win_k"][:, :, :, :true_len % W]).all()


# ------------------------------------------------------------------- kernel

RAGGED = [0, 1, 7, 8, 31, 32, 33, 40, 71, 96, 127]   # idle; edges of block, chunk, window


@pytest.mark.parametrize("attn_len", [64, 128])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 2e-2)])
def test_the_decode_kernel_reads_live_rows_only(monkeypatch, attn_len, dtype, tol):
    """Against `eva.decode_attention`, with NaN planted in every stale row."""
    monkeypatch.setattr(eva_decode, "_BLOCK_ROWS", 8)
    lens = jnp.asarray([n for n in RAGGED if n < attn_len], jnp.int32)
    B, H, hd, L, rows = len(lens), 4, 16, 3, W + 128 // C
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    q, kc, vc = (jax.random.normal(k, (B, H, hd)).astype(dtype) for k in ks[:3])
    k_all, v_all = (jax.random.normal(k, (L, B, H, rows, hd)).astype(dtype)
                    for k in ks[3:])
    win, summ = lens % W, lens // W * (W // C)
    r = jnp.arange(rows)[None]
    live = jnp.where(r < W, r < win[:, None], r - W < summ[:, None])
    want = eva.decode_attention(q, kc, vc, k_all[1], v_all[1], win, summ, W, hd ** -0.5)
    dead = lambda a: jnp.where(live[None, :, None, :, None], a, jnp.nan)
    got = eva_decode.eva_decode_attention(
        q, kc, vc, dead(k_all), dead(v_all), jnp.asarray(1),
        eva_decode.live_blocks(lens, W, C, attn_len), W, C, attn_len)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(vc[0], np.float32), atol=tol)   # idle: its own row


def test_write_rows_takes_a_named_row_and_row_zero():
    cache = jnp.zeros((2, 3, 1, 16, 8))
    rows = jnp.arange(2 * 3 * 8, dtype=jnp.float32).reshape(2, 3, 1, 8) + 1
    out = write_rows(cache, rows, jnp.asarray([0, 9, 3], jnp.int32),
                     jnp.asarray([True, True, False]))
    want = np.zeros((2, 3, 1, 16, 8), np.float32)
    want[:, 0, :, 0], want[:, 1, :, 9] = rows[:, 0], rows[:, 1]
    np.testing.assert_array_equal(out, want)
    # the default: row `lengths[b]`, a slot of length 0 writes nothing
    np.testing.assert_array_equal(
        write_rows(cache, rows, jnp.asarray([0, 5, 0], jnp.int32))[:, :, :, 5].sum(),
        rows[:, 1].sum())


# ------------------------------------------------------------------- engine

def test_staggered_slots_answer_as_each_request_alone(params):
    """Five requests over three slots, admitted at different steps, so that
    slots in one batch stand at different phases of chunk and window; every
    answer crosses a window close. Token for token what each gets alone."""
    rnd = np.random.default_rng(0)
    reqs = [(list(rnd.integers(1, 64, n)), m)
            for n, m in ((27, 12), (9, 40), (40, 30), (31, 9), (62, 20))]
    alone = []
    for prompt, m in reqs:
        eng = ContinuousBatchingEngine(params, CFG, num_slots=1, max_len=128)
        alone.append(eng.generate(prompt, max_new_tokens=m))
    eng = ContinuousBatchingEngine(params, CFG, num_slots=3, max_len=128)
    ids = []
    for prompt, m in reqs:
        ids.append(eng.submit(prompt, max_new_tokens=m))
        for _ in range(3):
            eng.step()
    eng.run_until_done()
    assert [eng.result(i) for i in ids] == alone


def test_the_engine_passes_prompts_in_whole_windows_and_reports_both_regions(params):
    from ray_tpu.util import tracing

    tracing.clear()
    eng = ContinuousBatchingEngine(params, CFG, num_slots=2, max_len=128)
    assert [eng._prompt_bucket(n) for n in (1, 32, 33, 90)] == [32, 32, 64, 96]
    eng.generate(list(range(1, 41)), max_new_tokens=30)
    spans = tracing.get_events()
    steps = [e for e in spans if e["name"] == "engine.step" and "window_rows" in e["args"]]
    first = steps[0]["args"]      # position 40: 8 rows of window 1, 8 summaries
    assert (first["window_rows"], first["summary_rows"]) == (8, 8)
    assert sum(s["args"].get("chunks_closed", 0) for s in steps) == (40 + 29) // C - 40 // C
    assert sum(s["args"].get("windows_closed", 0) for s in steps) == 1
    pre = next(e for e in spans if e["name"] == "engine.prefill")
    assert pre["args"]["bucket"] == 64 and pre["args"]["eva_layers"] == 3


def test_eva_cache_shapes_and_arguments():
    cache = CFG.make_cache(5, 128)
    assert isinstance(cache, hybrid.EvaCache)
    assert cache.state["ek"].shape == (3, 5, 4, W + 128 // C, 16)
    assert cache.state["ck"].shape == (3, 5, 4, C, 16)
    assert cache.step_args([40, 31, 64, 1], 64) == {
        "window_rows": 8 + 31 + 0 + 1, "summary_rows": 8 + 0 + 16 + 0}
    assert cache.prefill_args == {"eva_layers": 3} and cache.step_tokens == 1
    with pytest.raises(ValueError, match="EVA mixers make ONE run"):
        dataclasses.replace(CFG, eva_layers=(1, 2)).runs()


@pytest.mark.parametrize("make,want", [
    (lambda: DenseKVCache(ModelConfig.tiny(), 4, 64), {"live_rows": 14}),
    (lambda: HybridConfig.tiny_hybrid().make_cache(4, 64),
     {"state_slots": 2, "latent_rows": 14}),
    (lambda: HybridConfig.tiny_runs().make_cache(4, 64),
     {"state_slots": 2, "kv_rows": 14}),
])
def test_the_other_caches_report_what_they_reported(make, want):
    """The widened interface: `step_args` is given the busy slots' positions
    (it was given their number and sum), and a cache without `prompt_bucket`
    gets the engine's powers of two."""
    cache = make()
    assert cache.step_args([5, 9], 64) == want
    assert not hasattr(cache, "prompt_bucket")
    assert [_bucket_len(n, 64) for n in (3, 9, 40, 62)] == [8, 16, 63, 63]
