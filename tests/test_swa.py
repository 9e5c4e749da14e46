"""Sliding-window and full attention in one stack (`HybridConfig.tiny_swa()`:
two periods of window x 3, full, a window of 8 positions, 8 query heads on 2
key heads, every layer ONE LayerNorm whose rows attention and an expert
layer both read and add in parallel) against the plain float32 reference of
`perfbench/references/`: the whole sequence; prefill + decode through
`SwaCache` with the ring wrapped five times over; a slot reused by a shorter
request; a prompt pass of one, two and five chunks; the engine with short
and long prompts in one queue; the pieces (interleaved rotation, LayerNorm,
the parallel block); the banded flash kernel and the ring's decode kernel in
interpret mode; and the share test: eight chips' routed parts plus the
shared mean counted once add up to the uncut layer."""

import dataclasses
import importlib
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import hybrid
from ray_tpu.models.hybrid import HybridConfig
from ray_tpu.models.inference import _gqa_decode_attention
from ray_tpu.models.serving import ContinuousBatchingEngine
from ray_tpu.ops.layers import (apply_rotary, layer_norm, rotary_embedding,
                                rotate_interleaved)
from ray_tpu.ops.pallas import decode_attention, flash_attention
from ray_tpu.util import tracing

attention_ops = importlib.import_module("ray_tpu.ops.attention")   # the module, not its function

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = HybridConfig.tiny_swa()
W = CFG.swa_window          # 8
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the configuration file of the same model, in the reference's key names
C = {"hidden_size": 64, "num_hidden_layers": 8, "layer_types": PERIOD * 2,
     "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 16,
     "position_embedding_type": "rope_gptj", "rotary_pct": 1, "rope_theta": 5e4,
     "attention_bias": False, "use_qk_norm": False, "sliding_window": 8,
     "expert_selection_fn": "sigmoid", "norm_topk_prob": True,
     "num_experts_per_tok": 2, "num_shared_experts": 2, "intermediate_size": 32,
     "shared_expert_combination_strategy": "average", "hidden_act": "silu",
     "use_gated_activation": True, "use_parallel_block": True,
     "first_k_dense_replace": 0, "layer_norm_eps": 1e-5, "tie_word_embeddings": True,
     "logit_scale": 1, "vocab_size": 96,
     "experts_held": {"of": 8, "first": 0, "count": 8}}
S = 48     # positions of the test sequences: six windows


@pytest.fixture(scope="module")
def ref():
    from perfbench.lib.manifest import load_py

    return load_py(os.path.join(ROOT, "perfbench", "references", "command_a_plus.py"))


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


def test_the_stack_is_runs_of_window_and_full_layers_with_one_norm_a_layer(params):
    assert CFG.runs() == (("swa", 3), ("full", 1)) * 2
    assert CFG.run_ffns() == ("moe",) * 4 and CFG.windowed and CFG.scanned
    window, full = params["runs"][:2]
    assert set(window) == {"mixer_norm", "moe", "swa"} and set(full) == \
        {"mixer_norm", "moe", "full"}               # ONE norm, no `ffn_norm`
    assert set(window["moe"]) == {"router", "w_gate", "w_up", "w_down", "shared"}
    assert window["moe"]["shared"]["w_gate"].shape == (3, 64, 2 * 32)   # 2 experts, one MLP
    assert "lm_head" not in params                  # the head is the embedding
    assert isinstance(CFG.make_cache(2, 64), hybrid.SwaCache)
    with pytest.raises(ValueError, match="window .swa. and full attention mixers"):
        dataclasses.replace(CFG, router="argmax").runs()
    with pytest.raises(ValueError, match="window .swa. and full attention mixers"):
        dataclasses.replace(CFG, attn_layers=(4,), full_layers=(8,)).runs()
    # the other families' stacks are what they were
    assert HybridConfig.tiny_granite().runs()[0] == ("mamba2", 3)
    assert not HybridConfig.tiny_dsa().windowed


def test_whole_sequence_logits(ref, params, tokens, want):
    assert _rel(ref, hybrid.forward(params, tokens, CFG), want) < 2e-5


def test_the_window_matters(ref, params, tokens, want):
    """Every layer a full one, or a window twice as wide, is another function
    from the ninth position on; up to the eighth the band never binds."""
    wide = hybrid.forward(params, tokens, dataclasses.replace(CFG, swa_window=16))
    assert _rel(ref, wide[:, :W], want[:, :W]) < 2e-5
    assert _rel(ref, wide[:, W:], want[:, W:]) > 1e-2


@pytest.mark.parametrize("leaf", [("mixer_norm",), ("swa", "wo"), ("moe", "router"),
                                  ("moe", "shared", "w_down"), ("moe", "w_up")])
def test_a_weight_left_out_changes_the_logits(ref, params, tokens, want, leaf):
    def ones(tree, path):
        if len(path) == 1:
            return {**tree, path[0]: jnp.ones_like(tree[path[0]])}
        return {**tree, path[0]: ones(tree[path[0]], path[1:])}
    changed = {**params, "runs": [ones(params["runs"][0], leaf)] + params["runs"][1:]}
    assert _rel(ref, hybrid.forward(changed, tokens, CFG), want) > 1e-3


@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_a_prompt_pass_of_whole_chunks_is_the_reference(ref, params, tokens, want, chunks):
    """The prompt pass walks `chunks` chunks of one window (a loop whose trip
    count is data) and gives the logits at the last true position that the
    plain reference gives for the whole sequence; the rows it leaves (the
    full layers' a row a position, the window layers' the last 8 positions
    at their ring places) carry a decoded position to the reference's logits
    too."""
    cache = CFG.make_cache(2, 64)
    n = chunks * W - 3                       # true length: the last chunk is part padding
    bucket = cache.prompt_bucket(n)
    assert bucket == (W if chunks == 1 else 64)
    row = np.zeros((1, bucket), np.int32)
    row[0, :n] = np.asarray(tokens[0, :n])
    lens = jnp.asarray([n], jnp.int32)
    logits, rows = hybrid.prefill(params, jnp.asarray(row), lens, CFG)
    assert _rel(ref, logits[0], want[0, n - 1]) < 2e-5
    assert rows["k"].shape == (2, 1, 2, bucket, 16) and rows["wk"].shape == (6, 1, 2, W, 16)
    lengths, held = cache.write(jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
                                jnp.asarray([1]), rows, lens, jnp.asarray([0]))
    tok = jnp.zeros((2,), jnp.int32).at[1].set(tokens[0, n])
    _, stepped, _ = hybrid.decode_logits(params, cache.state, lengths, tok, None, CFG, 64)
    assert _rel(ref, stepped[1], want[0, n]) < 3e-5
    with pytest.raises(ValueError, match="one prompt a call"):
        hybrid.prefill(params, jnp.zeros((2, 64), jnp.int32), jnp.asarray([9, 9]), CFG)


def test_prefill_then_decode_wraps_the_ring_five_times(ref, params, tokens, want):
    """A prompt of 21 positions (the ring wrapped twice in the pass), then 27
    decoded positions through the slot state (three more wraps), every one's
    logits against the reference's whole forward; other slots live."""
    cache = CFG.make_cache(4, 64)
    lengths, held = jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32)
    for b, (n, slot) in enumerate([(21, 2), (5, 0)]):
        row = np.zeros((1, cache.prompt_bucket(n)), np.int32)
        row[0, :n] = np.asarray(tokens[b, :n])
        lens = jnp.asarray([n], jnp.int32)
        logits, rows = hybrid.prefill(params, jnp.asarray(row), lens, CFG)
        assert _rel(ref, logits[0], want[b, n - 1]) < 2e-5
        lengths, held = cache.write(lengths, held, jnp.asarray([slot]), rows, lens,
                                    jnp.asarray([0]))
    state = cache.state
    for t in range(27):
        tok = np.zeros((4,), np.int32)
        tok[2], tok[0] = int(tokens[0, 21 + t]), int(tokens[1, 5 + t])
        state, logits, routing = hybrid.decode_logits(
            params, state, lengths, jnp.asarray(tok), None, CFG, 64)
        lengths = lengths + (lengths > 0)
        assert routing.shape == (8, 4, 2)
        assert _rel(ref, logits[2], want[0, 21 + t]) < 3e-5, t
        assert _rel(ref, logits[0], want[1, 5 + t]) < 3e-5, t      # wraps at its 4th step
    assert lengths.tolist() == [32, 0, 48, 0]


def test_a_reused_slot_shows_no_stale_row(ref, params, tokens, want):
    """A slot that held 40 positions is given a prompt of 3: the ring's rows
    3..7 still hold the last occupant's keys, masked by the length until the
    new one's ring has wrapped over them."""
    cache = CFG.make_cache(2, 64)
    lengths, held = jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32)
    for b, n in [(0, 40), (1, 3)]:
        row = np.zeros((1, cache.prompt_bucket(n)), np.int32)
        row[0, :n] = np.asarray(tokens[b, :n])
        lens = jnp.asarray([n], jnp.int32)
        _, rows = hybrid.prefill(params, jnp.asarray(row), lens, CFG)
        lengths, held = cache.write(lengths, held, jnp.asarray([1]), rows, lens,
                                    jnp.asarray([0]))
    assert float(jnp.abs(cache.state["wk"][:, 1, :, 3:]).max()) > 0      # stale, still there
    state = cache.state
    for t in range(12):
        tok = jnp.asarray([0, int(tokens[1, 3 + t])], jnp.int32)
        state, logits, _ = hybrid.decode_logits(params, state, lengths, tok, None, CFG, 64)
        lengths = lengths + (lengths > 0)
        assert _rel(ref, logits[1], want[1, 3 + t]) < 3e-5, t


def test_padding_changes_nothing(params, tokens):
    row = np.zeros((1, 64), np.int32)
    row[0, :19] = np.asarray(tokens[0, :19])
    a, _ = hybrid.prefill(params, jnp.asarray(row), jnp.asarray([19]), CFG)
    row[0, 19:] = 5
    b, _ = hybrid.prefill(params, jnp.asarray(row), jnp.asarray([19]), CFG)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_short_and_long_requests_in_one_queue_through_the_engine(ref, params):
    """Short prompts (inside one window) and long ones (three to five
    windows) staggered over two slots answer as the reference's greedy
    continuation; the steps' span arguments are the positions' own, by kind."""
    tracing.clear()
    eng = ContinuousBatchingEngine(params, CFG, num_slots=2, max_len=64)
    prompts = [list(range(3, 3 + n)) for n in (5, 37, 8, 21, 3)]
    ids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_done()
    for rid, prompt in zip(ids, prompts):
        got = eng.result(rid)[len(prompt):]
        assert len(got) == 6
        want = ref.logits(params, jnp.asarray([prompt + got[:-1]]), C)[0, len(prompt) - 1:]
        # every answered token is the reference's own greedy choice
        assert float(jnp.max(jnp.max(want, -1) - want[jnp.arange(6), jnp.asarray(got)])) < 1e-4
    steps = [e["args"] for e in tracing.get_events()
             if e["name"] == "engine.step" and "window_rows" in e.get("args", {})]
    assert steps and all(0 < s["window_rows"] <= s["full_rows"] for s in steps)
    assert any(s["window_rows"] < s["full_rows"] for s in steps)
    assert all(s["window_rows"] <= 2 * W for s in steps)           # min(n, 8) a slot
    least = sum(min(len(p) + t, W) for p in prompts for t in range(5))
    assert least <= sum(s["window_rows"] for s in steps) <= least + 5 * W
    assert sum(s["full_rows"] for s in steps) >= sum(
        len(p) + t for p in prompts for t in range(5))
    assert sum(s.get("expert_assignments", 0) for s in steps) > 0
    passes = [e["args"] for e in tracing.get_events() if e["name"] == "engine.prefill"]
    assert all((a["window_layers"], a["full_layers"], a["chunk"]) == (6, 2, W)
               for a in passes)
    dispatched = [e["args"] for e in tracing.get_events()
                  if e["name"] == "engine.prefill_dispatch"]
    assert sorted(a["tokens"] for a in dispatched) == [3, 5, 8, 21, 37]
    # a prompt inside one window takes that bucket, every longer one the slot's
    assert sorted(a["bucket"] for a in dispatched) == [8, 8, 8, 64, 64]
    assert all(a["batch"] == 1 for a in dispatched)
    tracing.clear()


def test_cache_shapes_buckets_and_arguments():
    cache = CFG.make_cache(4, 64)
    assert cache.state["k"].shape == (2, 4, 2, 64, 16)      # a row a position
    assert cache.state["wk"].shape == (6, 4, 2, W, 16)      # a ring of 8 rows
    assert cache.counters == ("expert_assignments", "experts_touched")
    assert cache.step_tokens == 1 and cache.max_prefill_batch(8) == 1
    assert [cache.prompt_bucket(n) for n in (1, 8, 9, 40, 63)] == [8, 8, 64, 64, 64]
    assert cache.step_args([3, 20, 50], 64) == {"window_rows": 3 + 8 + 8,
                                                "full_rows": 73, "wrapped_slots": 2}
    big = dataclasses.replace(CFG, swa_window=4096).make_cache(1, 49152)
    assert [big.prompt_bucket(n) for n in (1, 512, 513, 3072, 4096, 4097, 48640)] == \
        [512, 512, 1024, 4096, 4096, 49152, 49152]
    assert big.state["wk"].shape[3] == 4096 and big.state["k"].shape[3] == 49152


def test_the_phases_are_named(params, tokens):
    text = hybrid.decode_step.lower(
        params, CFG.make_cache(2, 64).state, jnp.zeros((2,), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), bool), CFG, 64).as_text(debug_info=True)
    for scope in ("swa/window", "swa/full", "moe", "shared_expert", "state_write", "head"):
        assert scope in text, scope


# ------------------------------------------------------------------ pieces


def test_interleaved_rotation_is_the_half_rotation_under_a_permutation():
    """Lane 2i turns with lane 2i + 1: gathering the even lanes before the odd
    ones gives the half layout (`apply_rotary`: lane i with lane i + d/2);
    without the permutation the two differ, and scores are not kept."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 4, 16))
    pos = jnp.asarray([[0, 1, 2, 3, 4, 5, 6, 7, 70000]] * 2)
    got = rotate_interleaved(x, pos, 5e4)
    perm = np.concatenate([np.arange(0, 16, 2), np.arange(1, 16, 2)])
    half = apply_rotary(x[..., perm], *rotary_embedding(pos, 16, 5e4))
    np.testing.assert_allclose(np.asarray(got[..., perm]), np.asarray(half), atol=1e-5)
    assert float(jnp.abs(got - apply_rotary(x, *rotary_embedding(pos, 16, 5e4))).max()) > 0.1
    np.testing.assert_allclose(np.asarray(got[:, 0]), np.asarray(x[:, 0]), atol=1e-6)
    # a rotation: norms are kept, and q . k depends on the distance alone
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1), np.linalg.norm(x, axis=-1),
                               rtol=1e-5)
    q, k = x[0, :1], x[1, :1]
    dot = lambda a, b: float(jnp.sum(rotate_interleaved(q, jnp.asarray([a]), 5e4)
                                     * rotate_interleaved(k, jnp.asarray([b]), 5e4)))
    assert dot(5, 2) == pytest.approx(dot(105, 102), abs=1e-4)


def test_layer_norm_centres_scales_and_has_no_bias():
    x = jax.random.normal(jax.random.PRNGKey(0), (5, 64)) * 3.0 + 2.0
    w = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (64,))
    got = np.asarray(layer_norm(x, w, 1e-5))
    xn = np.asarray(x, np.float64)
    want = (xn - xn.mean(-1, keepdims=True)) / np.sqrt(xn.var(-1, keepdims=True) + 1e-5) \
        * np.asarray(w, np.float64)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(np.asarray(layer_norm(x + 7.0, w, 1e-5)), got, atol=1e-4)
    assert layer_norm(x.astype(jnp.bfloat16), w).dtype == jnp.bfloat16
    assert float(jnp.abs(layer_norm(jnp.zeros((2, 64)), w)).max()) == 0.0   # no bias


def test_the_block_adds_attention_and_experts_of_the_same_rows(ref, params, tokens):
    """x_(l+1) = x_l + A(h) + F(h), h ONE LayerNorm of x_l: a one-layer stack's
    hidden change is the reference's attention plus its expert layer of the
    same normed rows (a sequential block would feed F the rows attention
    changed)."""
    one = dataclasses.replace(CFG, n_layers=1, swa_layers=(1,), full_layers=())
    p1 = {**params, "runs": [jax.tree_util.tree_map(lambda a: a[:1], params["runs"][0])]}
    toks = tokens[:1, :24]
    x, _, _ = hybrid._sequence_swa(p1, toks, jnp.asarray([24]), one)
    lp = jax.tree_util.tree_map(lambda a: a[0], p1["runs"][0])
    x0 = params["embed"][toks[0]].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        h = ref._layer_norm(x0, lp["mixer_norm"], 1e-5)
        a = ref.attention(h, lp["swa"], "swa", {**C, "num_hidden_layers": 1})
        f, _ = ref.moe(h, lp["moe"], C)
    np.testing.assert_allclose(np.asarray(x[0]), np.asarray(x0 + a + f), atol=2e-5)
    h2 = ref._layer_norm(x0 + a, lp["mixer_norm"], 1e-5)
    assert float(jnp.abs(ref.moe(h2, lp["moe"], C)[0] - f).max()) > 1e-3


def test_the_eight_shares_add_up_to_the_uncut_layer(ref, params, tokens):
    """The guide's share test: eight chips each hold one of the eight experts
    (and the shared experts, which all compute alike). The eight routed parts
    plus the shared mean COUNTED ONCE are the uncut expert layer; the
    program's layer told which expert it holds is that chip's part."""
    lp = jax.tree_util.tree_map(lambda a: a[0], params["runs"][0])
    m = lp["moe"]
    h = jax.random.normal(jax.random.PRNGKey(3), (24, 64))
    with jax.default_matmul_precision("highest"):
        whole, _ = ref.moe(h, m, C)
        shared, _ = ref.moe(h, {**m, "w_gate": m["w_gate"][:0], "w_up": m["w_up"][:0],
                                "w_down": m["w_down"][:0]}, C, held=[])
        parts = []
        for e in range(8):
            mine = {**m, **{n: m[n][e:e + 1] for n in ("w_gate", "w_up", "w_down")}}
            routed, _ = ref.moe(h, mine, C, held=[e], shared=False)
            parts.append(routed)
            cfg_e = dataclasses.replace(CFG, experts_held=(e,))
            got, landed, touched, _ = hybrid._ffn(
                cfg_e, {"moe": mine}, h, h, jnp.ones((24,), bool))
            np.testing.assert_allclose(np.asarray(got), np.asarray(routed + shared),
                                       atol=2e-5)
            assert int(touched) <= 1
        np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(whole),
                                   atol=2e-5)
    assert float(jnp.abs(shared).max()) > 1e-3 and float(jnp.abs(sum(parts)).max()) > 1e-3


# ----------------------------------------------------------------- kernels


def _masked(q, k, v, layer, q_start, k_lo, window, scale):
    """The band as an explicit mask over grouped heads, float32."""
    b, H, sq, d = q.shape
    kl, vl = k[layer].astype(jnp.float32), v[layer].astype(jnp.float32)
    kvh = kl.shape[1]
    qg = q.astype(jnp.float32).reshape(b, kvh, H // kvh, sq, d)
    s = jnp.einsum("bgrqd,bgkd->bgrqk", qg, kl) * scale
    at = jnp.arange(sq)[:, None] + q_start
    cols = jnp.arange(kl.shape[2])[None, :]
    ok = (cols <= at) & (cols >= k_lo) & ((cols > at - window) if window else True)
    p = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
    return jnp.einsum("bgrqk,bgkd->bgrqd", p, vl).reshape(b, H, sq, d)


@pytest.mark.parametrize("window,q_start,k_lo", [
    (256, 256, 0),       # a window layer's chunk beside the chunk before it
    (256, 256, 256),     # the FIRST chunk: the half before it holds nothing
    (None, 512, 0),      # a full layer's third chunk over a cache of five
    (None, 0, 0),        # its first
    (384, 128, 64),      # a band that cuts blocks on both edges
])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
def test_the_banded_flash_kernel_is_the_mask(monkeypatch, window, q_start, k_lo, dtype, tol):
    """`flash_attention_banded` in interpret mode against the explicit mask:
    grouped heads through the index map (8 query heads on 2 key heads, the
    second layer of a two-layer cache), blocks of 128, key blocks wholly
    outside the band neither computed nor fetched (NaN planted in every row
    no query's band reaches)."""
    monkeypatch.setattr(attention_ops, "uses_flash_kernel", lambda q: True)
    b, H, kvh, sq, d = 1, 8, 2, 256, 128
    sk = 1280 if window is None else 512
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, H, sq, d)).astype(dtype)
    k, v = (jax.random.normal(kk, (2, b, kvh, sk, d)).astype(dtype) for kk in ks[1:])
    want = _masked(q, k, v, 1, q_start, k_lo, window, 0.09)
    lo = max(k_lo, q_start - window + 1) if window else k_lo
    reached = (jnp.arange(sk) >= lo // 128 * 128) & \
        (jnp.arange(sk) < (q_start + sq + 127) // 128 * 128)
    dead = lambda a: jnp.where(reached[None, None, None, :, None], a, jnp.nan).at[0].set(jnp.nan)
    real = flash_attention.flash_attention_banded
    monkeypatch.setattr(flash_attention, "flash_attention_banded",
                        lambda *a, **kw: real(*a, **kw, block_q=128, block_k=128))
    got = attention_ops.banded_attention(
        q, dead(k), dead(v), jnp.asarray(1), jnp.asarray(q_start), jnp.asarray(k_lo),
        window=window, sm_scale=0.09)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_banded_attention_without_the_kernel_is_the_mask():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 8, 16, 16))
    k, v = (jax.random.normal(kk, (3, 2, 2, 40, 16)) for kk in ks[1:])
    for window, q_start, k_lo in [(8, 16, 0), (8, 16, 16), (None, 24, 0)]:
        got = attention_ops.banded_attention(q, k, v, 2, q_start, k_lo, window=window,
                                             sm_scale=0.25)
        np.testing.assert_allclose(np.asarray(got), np.asarray(
            _masked(q, k, v, 2, q_start, k_lo, window, 0.25)), atol=1e-5)


RAGGED = [0, 1, 31, 32, 33, 100, 127, 128, 129, 200, 256, 1000]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
def test_the_decode_kernel_reads_a_ring_across_its_wrap(monkeypatch, dtype, tol):
    """`gqa_decode_attention` with `skip` over a ring of 128 rows in blocks of
    32 (interpret mode) against the masked einsum: slots before the wrap
    (rows [0, n) live, NaN planted behind them), at it, and far past it (every
    row live but the one at n % 128, which holds position n - 128: NaN there
    too, in the key AND the value)."""
    monkeypatch.setattr(decode_attention, "_BLOCK_ROWS", 32)
    Wr, B, kvh, rep, hd = 128, len(RAGGED), 2, 4, 128
    n = jnp.asarray(RAGGED, jnp.int32)
    held = jnp.minimum(n, Wr)
    skip = jnp.where(n >= Wr, n % Wr, -1)
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q = jax.random.normal(ks[0], (B, kvh, rep, hd)).astype(dtype)
    kc, vc = (jax.random.normal(k, (B, kvh, hd)).astype(dtype) for k in ks[1:3])
    k_all, v_all = (jax.random.normal(k, (2, B, kvh, Wr, hd)).astype(dtype) for k in ks[3:])
    rows = jnp.arange(Wr)[None, :]
    live = (rows < held[:, None]) & (rows != skip[:, None])
    want = _gqa_decode_attention(q.reshape(B, kvh * rep, 1, hd), k_all[1], v_all[1],
                                 kc, vc, live, 0.09)
    dead = lambda a: jnp.where(live[None, :, None, :, None], a, jnp.nan)
    got = decode_attention.gqa_decode_attention(
        q, kc, vc, dead(k_all), dead(v_all), jnp.asarray(1),
        decode_attention.live_items(held, Wr), Wr, 0.09, skip=skip)
    np.testing.assert_allclose(np.asarray(got.reshape(B, kvh * rep, hd), np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)
    # without `skip` the kernel is what it was: rows [0, n) and its own
    plain = decode_attention.gqa_decode_attention(
        q, kc, vc, jnp.where((rows < held[:, None])[None, :, None, :, None], k_all, jnp.nan),
        v_all, jnp.asarray(1), decode_attention.live_items(held, Wr), Wr, 0.09)
    want_plain = _gqa_decode_attention(q.reshape(B, kvh * rep, 1, hd), k_all[1], v_all[1],
                                       kc, vc, rows < held[:, None], 0.09)
    np.testing.assert_allclose(np.asarray(plain.reshape(B, kvh * rep, hd), np.float32),
                               np.asarray(want_plain, np.float32), atol=tol, rtol=tol)
