"""The GraniteMoeHybrid stack (`HybridConfig.tiny_granite()`: Mamba-2 and
attention mixers as scanned runs, an expert layer of which half the experts
are held beside a shared MLP in every layer, four multipliers away from 1)
against the plain float32 reference of `perfbench/references/`: the whole
sequence, prefill + decode through `RunsCache`, the engine end to end; one
test that fails without it a multiplier, the gated norm's order and the
softmax over the chosen; the share tied to the uncut layer."""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import hybrid
from ray_tpu.models.hybrid import HybridConfig
from ray_tpu.models.serving import ContinuousBatchingEngine
from ray_tpu.ops import moe
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = HybridConfig.tiny_granite()
# the configuration file of the same model, in the reference's key names
C = {"hidden_size": 64, "intermediate_size": 32, "shared_intermediate_size": 64,
     "layer_types": ["mamba", "mamba", "mamba", "attention"] * 2,
     "num_hidden_layers": 8, "mamba_n_heads": 8, "mamba_d_head": 16,
     "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_expand": 2,
     "mamba_chunk_size": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
     "position_embedding_type": "nope", "num_local_experts": 4,
     "num_experts_per_tok": 3, "experts_held": {"of": 8, "first": 0, "count": 4},
     "embedding_multiplier": 3.0, "attention_multiplier": 0.125,
     "residual_multiplier": 0.5, "logits_scaling": 4.0, "rms_norm_eps": 1e-5,
     "vocab_size": 512}


@pytest.fixture(scope="module")
def ref():
    from perfbench.lib.manifest import load_py

    return load_py(os.path.join(ROOT, "perfbench", "references",
                                "granite_moe_hybrid.py"))


@pytest.fixture(scope="module")
def params():
    return hybrid.init_params(jax.random.PRNGKey(7), CFG)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 45), 1, CFG.vocab_size)


@pytest.fixture(scope="module")
def want(ref, params, tokens):
    return ref.logits(params, tokens, C)


def _rel(ref, got, want):
    return float(ref.rel_err(got, want))


def test_the_stack_is_runs_of_like_layers_with_expert_ffns(ref):
    assert CFG.runs() == (("mamba2", 3), ("attn", 1), ("mamba2", 3), ("attn", 1))
    assert CFG.run_ffns() == ("moe",) * 4
    assert ref.runs(C) == list(CFG.runs())
    assert HybridConfig.tiny_runs().run_ffns() == ("dense",) * 5
    with pytest.raises(ValueError, match="Mamba-1, Mamba-2 .* expert layer"):
        dataclasses.replace(CFG, kda_layers=(1,), mamba2_layers=(2, 3, 5, 6, 7)).runs()
    with pytest.raises(ValueError, match="softmax"):
        hybrid.init_params(jax.random.PRNGKey(0),
                           dataclasses.replace(CFG, router="sigmoid"))


def test_whole_sequence_logits(ref, params, tokens, want):
    assert _rel(ref, hybrid.forward(params, tokens, CFG), want) < 2e-5


@pytest.mark.parametrize("field,neutral", [
    ("embed_scale", 1.0), ("residual_scale", 1.0), ("attn_scale", 0.0),
    ("logit_divisor", 1.0)])
def test_a_multiplier_left_out_fails(ref, params, tokens, want, field, neutral):
    """`embedding_multiplier` scales the embedding (not the tied head),
    `residual_multiplier` what mixer and FFN give (not the stream),
    `attention_multiplier` replaces 1 / sqrt(head width), `logits_scaling`
    DIVIDES: without any one of them the logits are another model's."""
    without = dataclasses.replace(CFG, **{field: neutral})
    assert _rel(ref, hybrid.forward(params, tokens, without), want) > 1e-2


def test_a_multiplier_in_the_wrong_place_fails(ref, params, tokens, want):
    """The scaled embedding read back through the tied head, or the logits
    multiplied: both are one constant off, which a relative error sees."""
    got = hybrid.forward(params, tokens, CFG)
    assert _rel(ref, got * CFG.embed_scale, want) > 0.5
    assert _rel(ref, got * CFG.logit_divisor ** 2, want) > 0.5


def test_the_gate_comes_before_the_norm(ref, params, tokens, want, monkeypatch):
    """g = RMSNorm_w(y * SiLU(z)) over all the channels: normalising first
    and gating afterwards is another function."""
    def norm_then_gate(cfg, m, y, z):
        g = hybrid.rms_norm(y, m["norm"], cfg.norm_eps) * jax.nn.silu(z.astype(jnp.float32))
        return g.astype(cfg.dtype) @ m["w_out"]

    monkeypatch.setattr(hybrid, "_mamba2_output", norm_then_gate)
    hybrid.forward.clear_cache()
    try:
        assert _rel(ref, hybrid.forward(params, tokens, CFG), want) > 1e-2
    finally:
        monkeypatch.undo()
        hybrid.forward.clear_cache()


def test_the_softmax_runs_over_the_chosen_only(ref, params, tokens, want, monkeypatch):
    def over_all(x, router_w, top_k):
        logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
        _, idx = jax.lax.top_k(logits, top_k)
        return idx.astype(jnp.int32), jnp.take_along_axis(
            jax.nn.softmax(logits, axis=-1), idx, axis=-1)

    monkeypatch.setattr(hybrid, "route_softmax_top_k", over_all)
    hybrid.forward.clear_cache()
    try:
        assert _rel(ref, hybrid.forward(params, tokens, CFG), want) > 1e-2
    finally:
        monkeypatch.undo()
        hybrid.forward.clear_cache()


def test_prefill_then_decode_through_the_slot_state(ref, params, tokens, want):
    """Two prompts of unequal length in ONE bucket, right-padded with tokens
    that must not matter, into slots 2 and 0 of four; then eight positions
    decoded through `RunsCache`: every row is the reference's full forward."""
    lens = jnp.asarray([20, 29], jnp.int32)
    bucket = jnp.where(jnp.arange(32)[None, :] < lens[:, None], tokens[:, :32], 77)
    logits, rows = hybrid.prefill(params, bucket, lens, CFG)
    for b, n in enumerate((20, 29)):
        assert _rel(ref, logits[b], want[b, n - 1]) < 2e-5
    cache = CFG.make_cache(4, 64)
    assert type(cache).__name__ == "RunsCache"
    assert [a.shape for a in cache.state["ssm"]] == [(3, 4, 16, 128)] * 2
    assert [a.shape for a in cache.state["conv"]] == [(3, 4, 3, 160)] * 2
    first, rows = cache.prefill(params, bucket, lens)
    assert np.array_equal(first, jnp.argmax(logits, -1))
    lengths, toks = cache.write(jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
                                jnp.asarray([2, 0]), rows, lens, first)
    assert lengths.tolist() == [29, 0, 20, 0]
    for t in range(8):
        nxt = np.zeros((4,), np.int32)
        nxt[2], nxt[0] = tokens[0, 20 + t], tokens[1, 29 + t]
        cache.state, got, chose = hybrid.decode_logits(
            params, cache.state, lengths, jnp.asarray(nxt), None, CFG, 64)
        lengths = lengths + (lengths > 0)
        assert _rel(ref, got[2], want[0, 20 + t]) < 2e-5
        assert _rel(ref, got[0], want[1, 29 + t]) < 2e-5
        assert chose.shape == (8, 4, 3)
    # the step the engine runs: greedy tokens, and the two counters behind them
    lengths, nxt, report = cache.decode(params, lengths, jnp.asarray(nxt), 64, {0: 0, 2: 0})
    assert report.shape == (4 + 2,) and cache.counters == \
        ("expert_assignments", "experts_touched")
    landed, touched = int(report[4]), int(report[5])
    # two busy slots x 8 layers x 3 experts a token, of which the held ones
    assert 0 < landed <= 2 * 8 * 3 and 0 < touched <= min(landed, 8 * 4)
    assert lengths.tolist() == [38, 0, 29, 0]


def test_padding_leaves_state_and_tail_alone(params, tokens):
    """Whatever stands past a request's true length in its bucket, the state
    rows it leaves are the same."""
    lens = jnp.asarray([13, 32], jnp.int32)
    ok = jnp.arange(32)[None, :] < lens[:, None]
    _, a = hybrid.prefill(params, jnp.where(ok, tokens[:, :32], 5), lens, CFG)
    _, b = hybrid.prefill(params, jnp.where(ok, tokens[:, :32], 400), lens, CFG)
    for x, y in zip(a["ssm"] + a["conv"], b["ssm"] + b["conv"]):
        np.testing.assert_allclose(x, y, atol=1e-6)
    keep = np.asarray(ok)[None, :, None, :, None]
    np.testing.assert_allclose(np.where(keep, a["k"], 0), np.where(keep, b["k"], 0),
                               atol=1e-6)


def test_padding_and_idle_slots_are_routed_nowhere(params, tokens):
    """The counters count true tokens only: a bucket's padding lands on no
    expert, and an all-idle step touches none."""
    lens = jnp.asarray([13, 32], jnp.int32)
    _, _, routing = hybrid._sequence_runs(params, tokens[:, :32], lens, CFG)
    assert [r.shape for r in routing] == [(3, 2, 32, 3), (1, 2, 32, 3)] * 2
    # what landed in a prompt pass is what the TRUE tokens chose of the held
    h32 = jnp.ones((32, CFG.d_model))
    lp = jax.tree_util.tree_map(lambda a: a[0], params["runs"][0])
    for n in (32, 13, 0):
        _, landed, _, chosen = hybrid._ffn(CFG, lp, h32, h32, jnp.arange(32) < n)
        assert int(landed) == int(jnp.sum(chosen[:n] < 4))
    cache = CFG.make_cache(4, 64)
    _, _, _, report = hybrid.decode_step(
        params, cache.state, jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
        None, CFG, 64)
    assert report[4:].tolist() == [0, 0]


def _greedy_gaps(ref, params, prompt, answer):
    """How far the reference's logit of each answered token lies under its
    top logit, teacher-forced over prompt + answer."""
    row = jnp.asarray([prompt + answer])
    logits = ref.logits(params, row, C)[0]
    at = np.arange(len(prompt) - 1, len(prompt) + len(answer) - 1)
    chosen = np.asarray(logits)[at, np.asarray(answer)]
    return np.asarray(logits)[at].max(-1) - chosen


def test_through_the_engine(ref, params):
    """Two slots, four requests of mixed lengths: through
    `ContinuousBatchingEngine` and `cfg.make_cache`, slots reused; every
    answered token is the reference's own greedy choice (its logit within
    1e-4 of the reference's top), and the step spans carry the cache's
    arguments and the two expert counters."""
    tracing.clear()
    eng = ContinuousBatchingEngine(params, CFG, num_slots=2, max_len=64)
    assert type(eng.cache).__name__ == "RunsCache"
    prompts = [[5, 9, 17, 300, 2, 2, 40, 41, 42, 43, 44], [7, 7, 3],
               list(range(100, 120)), [11, 12, 13, 14, 15, 16, 17, 18, 19]]
    ids = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, (6, 9, 4, 7))]
    eng.run_until_done()
    for p, i, n in zip(prompts, ids, (6, 9, 4, 7)):
        answer = eng.result(i)[len(p):]
        assert len(answer) == n
        assert _greedy_gaps(ref, params, p, answer).max() < 1e-4, p
    steps = [e["args"] for e in tracing.get_events() if e["name"] == "engine.step"]
    busy = [a for a in steps if a.get("active")]
    assert busy and all(a["state_slots"] == a["active"] and a["kv_rows"] > 0
                        for a in busy)
    assert sum(a.get("expert_assignments", 0) for a in steps) > 0
    assert all(a["experts_touched"] <= a["expert_assignments"]
               for a in steps if "experts_touched" in a)
    passes = [e["args"] for e in tracing.get_events()
              if e["name"] == "engine.prefill_dispatch"]
    assert sorted(a["tokens"] for a in passes) == sorted(len(p) for p in prompts)
    assert all(a["bucket"] >= a["tokens"] and a["batch"] == 1 for a in passes)


def test_the_two_shares_add_up_to_the_uncut_layer(ref, params):
    """What ties the share to the model: the held experts' part as each of
    the two chips of a stage computes it (ids 0-3 here, 4-7 there; the
    router's 8 outputs and 3 choices on both), plus the shared MLP counted
    once, is the uncut reference's layer."""
    key = jax.random.PRNGKey(5)
    h = jax.random.normal(key, (24, CFG.d_model))
    lp = jax.tree_util.tree_map(lambda a: a[1], params["runs"][0])["moe"]
    there = {k: jax.random.normal(jax.random.fold_in(key, i), lp[k].shape) * 0.1
             for i, k in enumerate(("w_gate", "w_up", "w_down"))}
    whole = {**lp, **{k: jnp.concatenate([lp[k], there[k]]) for k in there}}
    want, _ = ref.moe(h, whole, C, held=list(range(8)), shared=True)
    idx, w = moe.route_softmax_top_k(h, lp["router"], CFG.top_k)
    parts = []
    for held, ws in (((0, 1, 2, 3), lp), ((4, 5, 6, 7), there)):
        y, landed, _ = moe.dropless_moe(h, idx, w, ws["w_gate"], ws["w_up"],
                                        ws["w_down"], held, CFG.n_experts)
        parts.append((y, int(landed)))
    assert parts[0][1] + parts[1][1] == 24 * CFG.top_k      # every choice lands once
    s = lp["shared"]
    shared = hybrid.swiglu(h @ s["w_gate"], h @ s["w_up"]) @ s["w_down"]
    got = parts[0][0] + parts[1][0] + shared
    assert _rel(ref, got, want) < 1e-5
    # and one share alone is what the reference gives for that share
    here, _ = ref.moe(h, lp, C, held=[0, 1, 2, 3], shared=True)
    assert _rel(ref, parts[0][0] + shared, here) < 1e-5
    assert _rel(ref, parts[0][0] + shared, want) > 0.1


def test_the_reference_follows_a_forced_choice(ref, params, tokens):
    """`logits_routed` under the program's own routing is the reference's
    own forward (no violation); under a wrong expert it says by how much."""
    lens = jnp.full((2,), 32, jnp.int32)
    _, rows = hybrid.prefill(params, tokens[:, :32], lens, CFG, with_routing=True)
    routing = rows["routing"]
    assert routing.shape == (8, 2, 32, 3)
    own, none = ref.logits_routed(params, tokens[:, :32], C, routing)
    assert float(none) < 1e-5
    assert _rel(ref, own, ref.logits(params, tokens[:, :32], C)) < 1e-5
    worst = (jnp.argmin(jnp.zeros((8, 2, 32, 3)), -1, keepdims=True) + routing[..., :1] + 1) % 8
    _, far = ref.logits_routed(params, tokens[:, :32], C,
                               jnp.concatenate([routing[..., :2], worst], -1))
    assert float(far) > 0.1


def test_a_long_prompts_expert_layer_goes_a_block_at_a_time(ref, params, monkeypatch):
    """Past `_FFN_BLOCK` tokens the scanned expert layer takes its tokens a
    block at a time: the same logits."""
    toks = jax.random.randint(jax.random.PRNGKey(3), (1, 64), 1, CFG.vocab_size)
    want = hybrid.forward(params, toks, CFG)
    monkeypatch.setattr(hybrid, "_FFN_BLOCK", 16)
    hybrid.forward.clear_cache()
    try:
        assert _rel(ref, hybrid.forward(params, toks, CFG), want) < 1e-5
    finally:
        monkeypatch.undo()
        hybrid.forward.clear_cache()


def test_prompt_buckets_are_whole_blocks_past_2048():
    cache = dataclasses.replace(CFG).make_cache(2, 16384)
    assert [cache.prompt_bucket(n) for n in (1, 9, 1024, 1025, 2048, 2049, 8200,
                                             12288, 16000)] == \
        [8, 16, 1024, 2048, 2048, 4096, 10240, 12288, 16383]
    # a stack without Mamba-2 mixers keeps the engine's powers of two
    assert not hasattr(HybridConfig.tiny_runs().make_cache(2, 64), "prompt_bucket")
    assert HybridConfig.tiny_runs().make_cache(2, 64).counters == ()


def test_the_phases_are_named(params, tokens):
    """`ssd` (with `conv`, `scan` / `step`, `gated_norm` inside), `attention`,
    `moe`, `shared_expert`, `head` name the operations of the compiled
    modules."""
    lens = jnp.asarray([45, 45], jnp.int32)
    cache = CFG.make_cache(2, 64)
    texts = {
        "prefill": hybrid.prefill.lower(params, tokens, lens, CFG).as_text(debug_info=True),
        "decode_step": hybrid.decode_step.lower(
            params, cache.state, lens, lens, None, CFG, 64).as_text(debug_info=True)}
    for mode, inner in (("prefill", "scan"), ("decode_step", "step")):
        for scope in ("ssd/conv", f"ssd/{inner}", "ssd/gated_norm", "attention", "moe",
                      "shared_expert", "head"):
            assert f"{scope}/" in texts[mode] or f"{scope}\"" in texts[mode], (mode, scope)


def test_llm_replica_builds_the_granite_form():
    from ray_tpu.serve.llm import LLMReplica

    r = LLMReplica("tiny_granite", num_slots=2, max_len=32)
    assert len(r({"prompt": [1, 2, 3], "max_new_tokens": 3})) == 6
