"""Learned sparse attention (`ops/dsa.py`, `ops/pallas/dsa.py`) and the stack
that runs it (`HybridConfig.tiny_dsa()`: three sparse-attention layers whose
indexer chooses 16 rows, over expert layers WITHOUT a shared MLP, an untied
head) against the plain float32 reference of `perfbench/references/`: the
whole sequence, prefill + decode through `DsaCache`, the engine end to end;
the exact top-k against a sort, with and without ties; the four kernels in
interpret mode against the forms XLA runs."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import hybrid
from ray_tpu.models.hybrid import HybridConfig
from ray_tpu.models.serving import ContinuousBatchingEngine
from ray_tpu.ops import dsa
from ray_tpu.ops.pallas import dsa as kernels
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = HybridConfig.tiny_dsa()
EVERY_ROW = dataclasses.replace(CFG, dsa_topk=1 << 20)
# the configuration file of the same model, in the reference's key names
C = {"hidden_size": 64, "num_hidden_layers": 3, "num_attention_heads": 8,
     "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 1e4,
     "rms_norm_eps": 1e-6, "num_experts": 8, "num_experts_per_tok": 2,
     "norm_topk_prob": True, "moe_intermediate_size": 32, "vocab_size": 96,
     "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                   "indexer_num_kv_heads": 1, "topk": 16,
                   "q_chunk_size": 8, "kv_chunk_size": 8}}
S = 45     # positions of the test sequences: 29 of them choose among more than 16


@pytest.fixture(scope="module")
def ref():
    from perfbench.lib.manifest import load_py

    return load_py(os.path.join(ROOT, "perfbench", "references", "keye_vl2.py"))


@pytest.fixture(scope="module")
def params():
    return hybrid.init_params(jax.random.PRNGKey(7), CFG)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, S), 1, CFG.vocab_size)


@pytest.fixture(scope="module")
def want(ref, params, tokens):
    return ref.logits(params, tokens, C)


def _rel(ref, got, want):
    return float(ref.rel_err(got, want))


# -------------------------------------------------------------------- model


def test_the_stack_is_one_run_over_expert_layers_without_a_shared_mlp(params):
    assert CFG.runs() == (("dsa", 3),) and CFG.run_ffns() == ("moe",)
    assert "shared" not in params["runs"][0]["moe"] and "lm_head" in params
    listed = hybrid.init_params(jax.random.PRNGKey(0),
                                dataclasses.replace(HybridConfig(), n_shared=0))
    assert all("shared" not in p.get("moe", {}) for p in listed["layers"])
    assert hybrid.forward(listed, jnp.ones((1, 8), jnp.int32),
                          dataclasses.replace(HybridConfig(), n_shared=0)).shape == (1, 8, 512)
    with pytest.raises(ValueError, match="sparse-attention .dsa. mixers make ONE run"):
        dataclasses.replace(CFG, attn_layers=(2,), dsa_layers=(1, 3)).runs()
    with pytest.raises(ValueError, match="EVA or of sparse-attention"):
        dataclasses.replace(HybridConfig.tiny_runs(), kda_layers=(1,),
                            mamba_layers=(2, 4, 5, 6, 8)).runs()
    assert isinstance(CFG.make_cache(2, 64), hybrid.DsaCache)


def test_whole_sequence_logits(ref, params, tokens, want):
    assert _rel(ref, hybrid.forward(params, tokens, CFG), want) < 2e-5


def test_the_selection_matters(ref, params, tokens, want):
    """With every row chosen the logits are far from the reference's: the
    comparison sees the mechanism."""
    assert _rel(ref, hybrid.forward(params, tokens, EVERY_ROW), want) > 1e-2


@pytest.mark.parametrize("leaf", ["q_norm", "k_norm", "ki_norm", "ki_bias", "w_wi"])
def test_a_weight_left_out_changes_the_logits(ref, params, tokens, want, leaf):
    a = params["runs"][0]["dsa"]
    flat = jnp.zeros_like(a[leaf]) if leaf in ("ki_bias",) else jnp.ones_like(a[leaf])
    p = {**params, "runs": [{**params["runs"][0], "dsa": {**a, leaf: flat}}]}
    assert _rel(ref, hybrid.forward(p, tokens, CFG), want) > 1e-3


def test_a_short_sequence_is_the_stack_with_every_row_chosen(params, tokens):
    short = tokens[:, :CFG.dsa_topk]
    np.testing.assert_array_equal(np.asarray(hybrid.forward(params, short, CFG)),
                                  np.asarray(hybrid.forward(params, short, EVERY_ROW)))
    # and through the cache: a slot of fewer than topk rows lists them all
    for cfg in (CFG, EVERY_ROW):
        cache = cfg.make_cache(2, 64)
        lens = jnp.asarray([9, 12], jnp.int32)
        first, rows = cache.prefill(params, jnp.pad(tokens[:, :12], ((0, 0), (0, 4))), lens)
        L, T = cache.write(jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32),
                           jnp.arange(2), rows, lens, first)
        out = []
        for _ in range(3):
            L, T, _ = cache.decode(params, L, T, 64, {0: None, 1: None})
            out.append(np.asarray(T))
        if cfg is CFG:
            mine = out
    np.testing.assert_array_equal(np.asarray(mine), np.asarray(out))


def test_prefill_then_decode_through_the_slot_state(ref, params, tokens, want):
    """Prompts of 20 and 29 tokens in a bucket of 32 go into slots 3 and 1 of
    a 4-slot cache; every later token is decoded through the cache,
    teacher-forced, and matches the reference's whole-sequence logits."""
    cache = CFG.make_cache(4, 64)
    lens = jnp.asarray([20, 29], jnp.int32)
    prompt = jnp.where(jnp.arange(32)[None] < lens[:, None],
                       jnp.pad(tokens, ((0, 0), (0, 0)))[:, :32], 0)
    logits, rows = hybrid.prefill(params, prompt, lens, CFG, with_routing=True)
    for i, n in enumerate((20, 29)):
        assert _rel(ref, logits[i], want[i, n - 1]) < 2e-5
    assert rows["chosen"].shape == (3, 2, 1, 32) and rows["routing"].shape == (3, 2, 32, 2)
    state = {k: rows[k] for k in ("kv", "ik")}
    slots = jnp.asarray([3, 1], jnp.int32)
    L, T = cache.write(jnp.zeros(4, jnp.int32), jnp.zeros(4, jnp.int32), slots, state,
                       lens, jnp.zeros(2, jnp.int32))
    for t in range(S - 29):
        toks = jnp.zeros(4, jnp.int32).at[slots].set(
            jnp.stack([tokens[0, 20 + t], tokens[1, 29 + t]]))
        cache.state, out, routing, (listed, count, own) = hybrid.decode_logits(
            params, cache.state, L, toks, None, CFG, 64)
        for i, (slot, n) in enumerate(((3, 20), (1, 29))):
            assert _rel(ref, out[slot], want[i, n + t]) < 2e-5, (i, t)
        # the list: min(n, topk) rows a busy slot, one fewer where the
        # position's own row is among the best; none of an idle one
        n = np.asarray(L)
        np.testing.assert_array_equal(
            np.asarray(count),
            np.broadcast_to(np.minimum(n, 16), (3, 4))
            - np.asarray(own & (n >= 16)[None]))
        assert np.asarray(count)[:, [0, 2]].sum() == 0 and routing.shape == (3, 4, 2)
        assert np.asarray(own)[:, [0, 2]].all() and listed.shape == (3, 4, 16)
        L = L + (L > 0)


def test_a_reused_slot_shows_no_stale_indexer_key(params, tokens):
    """A slot that held 40 positions takes a prompt of 18: the step's answer
    is that of a fresh cache, though rows 32.. still hold the old keys."""
    def run(cache):
        lens = jnp.asarray([18], jnp.int32)
        first, rows = cache.prefill(params, jnp.pad(tokens[1:, :18], ((0, 0), (0, 14))),
                                    lens)
        L, T = cache.write(jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32),
                           jnp.asarray([1]), rows, lens, first)
        out = []
        for _ in range(4):
            L, T, _ = cache.decode(params, L, T, 64, {1: None})
            out.append(int(T[1]))
        return out

    used = CFG.make_cache(2, 64)
    lens = jnp.asarray([40], jnp.int32)
    first, rows = used.prefill(params, jnp.pad(tokens[:1, :40], ((0, 0), (0, 24))), lens)
    used.write(jnp.zeros(2, jnp.int32), jnp.zeros(2, jnp.int32), jnp.asarray([1]),
               rows, lens, first)
    assert float(jnp.abs(used.state["ik"][:, 1, 0, 32:40]).sum()) > 0
    assert run(used) == run(CFG.make_cache(2, 64))


def test_padding_changes_nothing(params, tokens):
    lens = jnp.asarray([20, 29], jnp.int32)
    a, _ = hybrid.prefill(params, tokens[:, :32], lens, CFG)
    b, _ = hybrid.prefill(params, jnp.pad(tokens[:, :29], ((0, 0), (0, 35))), lens, CFG)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_through_the_engine(ref, params):
    """Staggered requests through `ContinuousBatchingEngine` answer as the
    reference's greedy continuation; the steps' span arguments are the
    positions' own."""
    tracing.clear()
    eng = ContinuousBatchingEngine(params, CFG, num_slots=2, max_len=128)
    prompts = [list(range(3, 3 + n)) for n in (21, 40, 9)]
    ids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_done()
    for rid, prompt in zip(ids, prompts):
        got = eng.result(rid)[len(prompt):]
        assert len(got) == 6
        want = ref.logits(params, jnp.asarray([prompt + got[:-1]]), C)[0, len(prompt) - 1:]
        # every answered token is the reference's own greedy choice
        assert float(jnp.max(jnp.max(want, -1) - want[jnp.arange(6), jnp.asarray(got)])) < 1e-4
    steps = [e["args"] for e in tracing.get_events()
             if e["name"] == "engine.step" and "index_rows" in e.get("args", {})]
    assert steps and all(s["kv_rows"] == s["index_rows"] >= s["selected_rows"] > 0
                         for s in steps)
    # `selected_rows` over the run is what the positions say: every decoded
    # position n of every request reads min(n, 16) rows (the first token of
    # an answer comes from the prompt pass; a request's last step is
    # dispatched before its last token is reaped, so it may run once more)
    least = sum(min(len(p) + t, 16) for p in prompts for t in range(5))
    assert least <= sum(s["selected_rows"] for s in steps) <= least + 3 * 16
    assert sum(s["kv_rows"] for s in steps) >= sum(
        len(p) + t for p in prompts for t in range(5))
    assert sum(s.get("expert_assignments", 0) for s in steps) > 0
    passes = [e["args"] for e in tracing.get_events() if e["name"] == "engine.prefill"]
    assert all(a["dsa_layers"] == 3 for a in passes)
    dispatched = [e["args"] for e in tracing.get_events()
                  if e["name"] == "engine.prefill_dispatch"]
    assert sorted(a["tokens"] for a in dispatched) == [9, 21, 40]
    assert sorted(a["bucket"] for a in dispatched) == [16, 32, 64]
    tracing.clear()


def test_cache_shapes_buckets_and_arguments():
    cache = CFG.make_cache(4, 256)
    assert cache.state["kv"].shape == (3, 4, 256, 4, 16)
    assert cache.state["ik"].shape == (3, 4, 1, 256, 128)
    assert [cache.prompt_bucket(n) for n in (1, 9, 33, 64, 65, 129, 250)] == \
        [8, 16, 64, 64, 128, 192, 256]
    assert cache.step_args([5, 40], 64) == {"kv_rows": 45, "index_rows": 45,
                                            "selected_rows": 21}
    assert cache.counters == ("expert_assignments", "experts_touched")
    assert cache.prefill_args == {"dsa_layers": 3} and cache.step_tokens == 1
    real = dataclasses.replace(CFG, dsa_chunk=512)
    assert [real.make_cache(1, 32768).prompt_bucket(n)
            for n in (8192, 8193, 14000, 32256)] == [8192, 12288, 16384, 32768]


def test_the_phases_are_named(params, tokens):
    lens = jnp.full((2,), S, jnp.int32)
    cache = CFG.make_cache(2, 64)
    texts = {
        "forward": hybrid.forward.lower(params, tokens, CFG),
        "prefill": hybrid.prefill.lower(params, tokens, lens, CFG),
        "decode_step": hybrid.decode_step.lower(
            params, cache.state, lens, tokens[:, 0], None, CFG, 64)}
    for mode, lowered in texts.items():
        text = lowered.as_text(debug_info=True)
        # (`lax.map` over the prompts starts the names of its body anew)
        for scope in ("dsa/index", "select/", "attend/", "moe", "head"):
            assert scope in text, (mode, scope)
        assert "shared_expert" not in text


# ---------------------------------------------------------------------- ops


def _prompt(key, n, H=4, kvh=2, hd=128, J=2, di=64, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    q = jax.random.normal(ks[0], (n, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (n, kvh, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (n, kvh, hd)).astype(dtype)
    qi = jax.random.normal(ks[3], (n, J, di)).astype(dtype)
    wi = jax.random.normal(ks[4], (n, J))
    ki = jnp.pad(jax.random.normal(ks[5], (n, di)).astype(dtype),
                 ((0, 0), (0, dsa.key_width(di) - di)))
    return q, k, v, qi, wi, ki


def test_chunked_scoring_is_the_unchunked():
    q, k, v, qi, wi, ki = _prompt(jax.random.PRNGKey(3), 64, hd=16, di=8)
    ki = ki[:, :8]
    a, rows_a = dsa._chunked(q, k, v, qi, wi, ki, 16, 8, 0.25, True)
    b, rows_b = dsa._chunked(q, k, v, qi, wi, ki, 16, 64, 0.25, True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6)
    np.testing.assert_array_equal(np.asarray(rows_a), np.asarray(rows_b))
    # 16 rows a query past the 16th, every causal row before
    chosen = np.asarray(rows_a).view(np.uint32)
    counts = [sum(int(chosen[t // 32, s]) >> (t % 32) & 1 for s in range(64))
              for t in range(64)]
    assert counts == [min(t + 1, 16) for t in range(64)]


def _sorted_choice(scores, topk):
    """The k best causal rows of every query by a stable sort: ties to the
    lower index."""
    n = scores.shape[0]
    causal = np.tril(np.ones((n, n), bool))
    s = np.where(causal, scores, -np.inf)
    order = np.argsort(-s, axis=1, kind="stable")[:, :topk]
    chosen = np.zeros((n, n), bool)
    np.put_along_axis(chosen, order, True, axis=1)
    return chosen & causal


@pytest.mark.parametrize("ties", [False, True])
def test_the_select_kernel_is_the_exact_top_k(monkeypatch, ties):
    """`dsa_select` against a sort, at float32 scores without ties and at
    scores on a coarse grid (hundreds of equal keys at the threshold). Every
    indexer query has ONE lane that is not zero, and the indexer one head, so a
    score is two products whatever the order: numpy computes the kernel's own bits."""
    monkeypatch.setattr(kernels, "SELECT_BLOCK", 32)
    monkeypatch.setattr(kernels, "KEY_BLOCK", 128)
    n, topk, J, di = 256, 48, 1, 64
    rng = np.random.default_rng(5)
    lane = rng.integers(0, di, (n, J))
    val, wi = (rng.standard_normal((n, J)).astype(np.float32) for _ in range(2))
    ki = rng.standard_normal((n, di)).astype(np.float32)
    if ties:   # scores are small integers, a quarter of them zero
        val, wi, ki = np.round(val), np.round(2 * wi), np.round(ki)
    else:      # no product is cut to zero
        val, ki = np.abs(val) + 0.1, np.abs(ki) + 0.1
    qi = np.zeros((n, J, di), np.float32)
    np.put_along_axis(qi, lane[..., None], val[..., None], axis=2)
    thr, tie = kernels.select(jnp.asarray(qi), jnp.asarray(wi),
                              jnp.pad(jnp.asarray(ki), ((0, 0), (0, 64))), topk)
    terms = [wi[:, j, None] * np.maximum(val[:, j, None] * ki[:, lane[:, j]].T, 0)
             for j in range(J)]
    scores = terms[0].astype(np.float32) + np.float32(0)   # no -0.0
    key = scores.view(np.int32).astype(np.int64)
    key = np.where(key < 0, key ^ 0x7FFFFFFF, key)
    cols = np.arange(n)[None]
    thr, tie = np.asarray(thr), np.asarray(tie)
    got = ((key > thr) | ((key == thr) & (cols <= tie))) & (cols <= np.arange(n)[:, None])
    np.testing.assert_array_equal(got, _sorted_choice(scores, topk))
    assert (got.sum(1) == np.minimum(np.arange(n) + 1, topk)).all()
    assert (tie < n).any() == ties


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
def test_the_prompt_kernels_are_the_chunked_form(monkeypatch, dtype, tol):
    monkeypatch.setattr(kernels, "SELECT_BLOCK", 32)
    monkeypatch.setattr(kernels, "QUERY_BLOCK", 64)
    monkeypatch.setattr(kernels, "KEY_BLOCK", 128)
    n, topk = 256, 40
    q, k, v, qi, wi, ki = _prompt(jax.random.PRNGKey(9), n, dtype=dtype)
    want, want_rows = dsa._chunked(q, k, v, qi, wi, ki[:, :64], topk, 64, 0.09, True)
    got, got_rows = kernels.prompt_attention(q, k, v, qi, wi, ki, topk=topk,
                                             scale=0.09, with_rows=True)
    causal = np.tril(np.ones((n, n), bool))
    unpack = lambda w: ((np.asarray(w).view(np.uint32)[np.arange(n) // 32]
                         >> (np.arange(n) % 32)[:, None].astype(np.uint32)) & 1
                        ).astype(bool) & causal
    if dtype == jnp.float32:
        np.testing.assert_array_equal(unpack(got_rows), unpack(want_rows))
    assert (unpack(got_rows).sum(1) == np.minimum(np.arange(n) + 1, topk)).all()
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    plain, _ = kernels.prompt_attention(q, k, v, qi, wi, ki, topk=topk, scale=0.09,
                                        with_rows=False)
    np.testing.assert_array_equal(np.asarray(plain, np.float32),
                                  np.asarray(got, np.float32))


RAGGED = [0, 1, 7, 31, 32, 33, 100, 127, 128]   # idle; edges of a block; full


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
def test_the_decode_kernels_read_live_and_listed_rows_only(monkeypatch, dtype, tol):
    """`dsa_scores` and `dsa_rows` against the forms XLA runs, with NaN
    planted in every indexer key past a slot's length and in every K/V row
    the list does not name."""
    monkeypatch.setattr(kernels, "SCORE_ROWS", 32)
    monkeypatch.setattr(kernels, "LIST_ROWS", 8)
    lens = jnp.asarray(RAGGED, jnp.int32)
    B, L, n, topk, kvh, rep, hd, J, di = len(RAGGED), 2, 128, 24, 2, 4, 128, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 8)
    qi = jax.random.normal(ks[0], (B, J, di)).astype(dtype)
    wi = jax.random.normal(ks[1], (B, J))
    ki_cur = jax.random.normal(ks[2], (B, 128)).astype(dtype)
    ik = jnp.pad(jax.random.normal(ks[3], (L, B, 1, n, di)),
                 ((0, 0),) * 4 + ((0, 128 - di),)).astype(dtype)
    live = (jnp.arange(n)[None] < lens[:, None])[None, :, None, :, None]
    scores = kernels.decode_scores(qi, wi, jnp.where(live, ik, jnp.nan),
                                   jnp.asarray(1), lens, n)
    want = jnp.where(live[0, :, 0, :, 0],
                     dsa.index_scores(qi[:, None], wi[:, None], ik[1, :, 0, :, :di])[:, 0],
                     -jnp.inf)
    np.testing.assert_allclose(np.asarray(scores), np.asarray(want), atol=tol, rtol=tol)

    rows, count, own = dsa.decode_select(qi, wi, ki_cur, ik, jnp.asarray(1), lens, n, topk)
    np.testing.assert_array_equal(
        np.asarray(count), np.minimum(RAGGED, topk) - np.asarray(own & (lens >= topk)))
    q = jax.random.normal(ks[4], (B, kvh, rep, hd)).astype(dtype)
    kc, vc = (jax.random.normal(k, (B, kvh, hd)).astype(dtype) for k in ks[5:7])
    kv = jax.random.normal(ks[7], (L, B, n, 2 * kvh, hd)).astype(dtype)
    want = dsa.decode_attention(q, kc, vc, kv, jnp.asarray(1), rows, count, own, 0.09)
    listed = np.zeros((B, n), bool)
    for b in range(B):
        listed[b, np.asarray(rows)[b, :int(count[b])]] = True
    dead = jnp.where(jnp.asarray(listed)[None, :, :, None, None], kv, jnp.nan)
    got = kernels.decode_attention(q, kc, vc, dead, jnp.asarray(1), rows, count, own, 0.09)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(jnp.broadcast_to(vc[0][:, None], got[0].shape),
                                          np.float32), atol=tol)   # idle: its own row
