"""The hybrid model (KDA + MLA mixers, dropless experts) against the plain
float32 reference `perfbench/references/kimi_linear.py`, at a toy of the
benchmark's pattern: dense layer + KDA, KDA, MLA, KDA; 8 experts, top-2, one
shared; float32, seeded random weights.

Tolerances: everything is float32 on the CPU, and program and reference
order their sums differently (chunked against token by token, absorbed
against expanded, sorted groups against a loop over experts), so agreement
is to a few float32 roundings accumulated over four layers: relative errors
of 1e-6 to 1e-5 were read; the limit 1e-4 leaves room and is still 40 times
under what one bf16 rounding of any operand gives (4e-3)."""

import collections
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import ModelConfig, hybrid, init_params
from ray_tpu.models.serving import ContinuousBatchingEngine
from ray_tpu.ops import kda, mla
from ray_tpu.ops.moe import dropless_moe, route_top_k
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "kimi_linear_reference",
    os.path.join(ROOT, "perfbench", "references", "kimi_linear.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

TOL = 1e-4
CFG = hybrid.HybridConfig.tiny_hybrid()
# the same toy in the configuration file's key names, for the reference
C = {"hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
     "num_hidden_layers": 4, "num_attention_heads": 2, "kv_lora_rank": 32,
     "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
     "num_experts": 8, "num_experts_per_token": 2, "first_k_dense_replace": 1,
     "routed_scaling_factor": 2.446, "moe_renormalize": True,
     "rms_norm_eps": 1e-5, "vocab_size": 512,
     "linear_attn_config": {"kda_layers": [1, 2, 4], "full_attn_layers": [3],
                            "head_dim": 16, "num_heads": 2,
                            "short_conv_kernel_size": 4},
     "experts_held": {"of": 8, "first": 0, "count": 8}}


@pytest.fixture(scope="module")
def params():
    return hybrid.init_params(jax.random.PRNGKey(0), CFG)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 160), 1, CFG.vocab_size)


@pytest.fixture(scope="module")
def want(params, tokens):
    return ref.logits(params, tokens, C)


def rel(got, want):
    return float(ref.rel_err(jnp.asarray(got), jnp.asarray(want)))


def test_layer_pattern_matches_the_reference():
    assert list(CFG.layer_kinds()) == ref.layer_kinds(C) == [
        ("kda", "dense"), ("kda", "moe"), ("mla", "moe"), ("kda", "moe")]


def test_whole_sequence_logits(params, tokens, want):
    # 160 positions: the chunked KDA crosses two chunk boundaries (64, 128)
    assert rel(hybrid.forward(params, tokens, CFG), want) < TOL


def test_prefill_then_decode_through_the_slot_state(params, tokens, want):
    """A prompt of 70 in a bucket of 128, written into slot 1 of a cache of
    two, then 30 tokens teacher-forced one at a time from that state: the
    logits of the prefill and of EVERY decoded position are the
    reference's full forward pass."""
    n = 70
    row = np.zeros((1, 128), np.int32)
    row[0, :n] = np.asarray(tokens[0, :n])
    logits, rows = hybrid.prefill(params, jnp.asarray(row), jnp.asarray([n]), CFG)
    assert rel(logits[0], want[0, n - 1]) < TOL
    cache = hybrid.HybridCache(CFG, 2, 256)
    lengths, toks = cache.write(
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32), jnp.asarray([1]),
        rows, jnp.asarray([n]), jnp.argmax(logits, -1).astype(jnp.int32))
    assert lengths.tolist() == [0, n]
    state = cache.state
    for t in range(n, n + 30):
        state, got, _ = hybrid.decode_logits(
            params, state, lengths, jnp.asarray([0, int(tokens[0, t])]),
            jnp.asarray([False, True]), CFG, 128)
        lengths = lengths + 1
        assert rel(got[1], want[0, t]) < TOL, t


def _layer_bodies(lowered):
    """{body: (private functions, calls)} of the list form's layer bodies in
    a lowering's StableHLO (a second function of a name is `name_<n>`)."""
    text = lowered.as_text()
    kind = r"@(_kda_seq|_kda_step|_mla_seq|_mla_step|_ffn_rows)(?:_\d+)?\("
    defs = collections.Counter(re.findall(r"func\.func private " + kind, text))
    calls = collections.Counter(re.findall(r"call " + kind, text))
    return {k: (defs[k], calls[k]) for k in defs}


def _traced_anew():
    """(`layers`, `layer_bodies_traced`) on the newest `xla.compile` span."""
    a = [e["args"] for e in tracing.get_events() if e["name"] == "xla.compile"][-1]
    return a["layers"], a["layer_bodies_traced"]


def test_a_program_holds_one_body_a_kind_of_layer(params):
    """Three KDA layers call ONE private `_kda_step` (`_kda_seq` in the
    prompt pass), the MLA layer its own, the four FFN halves two `_ffn_rows`
    (dense; experts + shared). A second decode program that differs in
    `attn_len` alone traces the MLA step, which reads it, and nothing else."""
    jax.clear_caches()   # a body another test traced at these shapes is a hit
    tracing.record_compiles()
    i4 = jax.ShapeDtypeStruct((4,), jnp.int32)
    state = CFG.make_cache(4, 64).state
    step = lambda attn_len: hybrid.decode_step.lower(
        params, state, i4, i4, jax.ShapeDtypeStruct((4,), jnp.bool_), CFG, attn_len)
    assert _layer_bodies(step(32)) == {
        "_kda_step": (1, 3), "_mla_step": (1, 1), "_ffn_rows": (2, 4)}
    assert _traced_anew() == (4, 4)
    assert _layer_bodies(step(64)) == {
        "_kda_step": (1, 3), "_mla_step": (1, 1), "_ffn_rows": (2, 4)}
    assert _traced_anew() == (4, 1)
    prompt = hybrid._prefill_first.lower(
        params, jax.ShapeDtypeStruct((2, 16), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32), CFG)
    assert _layer_bodies(prompt) == {
        "_kda_seq": (1, 3), "_mla_seq": (1, 1), "_ffn_rows": (2, 4)}
    assert _traced_anew() == (4, 4)


def _kda_inputs(seed, b, s, H, dk):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = kda.l2_norm(jax.random.normal(ks[0], (b, s, H, dk))) * dk ** -0.5
    k = kda.l2_norm(jax.random.normal(ks[1], (b, s, H, dk)))
    v = jax.random.normal(ks[2], (b, s, H, dk))
    g = -jax.random.uniform(ks[3], (b, s, H, dk), minval=1e-3, maxval=3.0)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, s, H)))
    return q, k, v, g, beta


@pytest.mark.parametrize("s,true_len", [(150, 150), (128, 70), (64, 3), (8, 8)])
def test_kda_chunked_is_the_recurrence(s, true_len):
    """Chunked (chunks of 64, across a boundary) = the reference's token by
    token recurrence; positions past true_len (beta 0, g 0) leave S alone;
    decays down to exp(-3 x 64) inside one chunk do not overflow."""
    q, k, v, g, beta = _kda_inputs(3, 2, s, 2, 16)
    live = (jnp.arange(s) < true_len)[None, :, None]
    g, beta = jnp.where(live[..., None], g, 0.0), jnp.where(live, beta, 0.0)
    o, S = kda.kda_chunked(q, k, v, g, beta, chunk=64)
    want = ref.kda_recurrence(q, k, v, g, beta)
    assert rel(o[:, :true_len], want[:, :true_len]) < TOL
    # the state after the padded bucket = the state after the true sequence
    _, S_true = kda.kda_chunked(*(a[:, :true_len] for a in (q, k, v, g, beta)),
                                chunk=64)
    assert rel(S, S_true) < TOL


@pytest.mark.parametrize("n,kind", [(64, "random"), (64, "ones"), (16, "ones"),
                                    (24, "random")])
def test_inv_unit_lower_is_the_inverse(n, kind):
    """(I + A)^-1 by forward substitution in blocks of 16 and pairwise
    merges; "ones" is the worst case of the delta rule (the same key again
    and again, beta 1, no decay), whose inverse is 1 on the diagonal and -1
    under it; 24 is no power-of-two number of blocks (one block)."""
    A = np.tril(np.ones((n, n)) if kind == "ones" else
                np.random.default_rng(5).normal(size=(2, 3, n, n)) * 0.3, -1)
    got = np.asarray(kda._inv_unit_lower(jnp.asarray(A, jnp.float32)))
    want = np.linalg.inv(np.eye(n) + A)
    assert np.abs(got - want).max() < 1e-4 * max(1.0, np.abs(want).max())


def test_kda_one_token_is_the_recurrence():
    q, k, v, g, beta = _kda_inputs(4, 2, 70, 2, 16)
    S = jnp.zeros((2, 2, 16, 16))
    outs = []
    for t in range(70):
        S, o = kda.kda_step(S, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        outs.append(o)
    assert rel(jnp.stack(outs, 1), ref.kda_recurrence(q, k, v, g, beta)) < TOL
    _, S_chunked = kda.kda_chunked(q, k, v, g, beta, chunk=64)
    assert rel(S, S_chunked) < TOL


def test_conv_step_is_the_sequence_convolution():
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 9, 6))
    w = jax.random.normal(jax.random.PRNGKey(6), (4, 6))
    want = kda.short_conv(x, w)
    tail = kda.conv_tail(x[:, :5], jnp.asarray([5, 2]), 4)
    assert np.allclose(tail[0], x[0, 2:5]) and np.allclose(tail[1, 0], 0.0)
    y, tail0 = kda.short_conv_step(x[:, 5][:1], tail[:1], w)
    assert np.allclose(y[0], want[0, 5], atol=1e-5)
    assert np.allclose(tail0[0], x[0, 3:6])


def test_mla_absorbed_is_expanded(params):
    """One token against the latent cache (the key half of W_kvb folded into
    the query, the value half applied after the sum) = the reference's
    expanded attention at that position."""
    p = params["layers"][2]["mla"]
    h = jax.random.normal(jax.random.PRNGKey(7), (1, 40, CFG.d_model))
    want = ref._mla(h, p, C, q_block=40)[0, -1]
    q, latent = hybrid._mla_latent(CFG, p, h, jnp.arange(40))
    # one layer's cache of one slot, one "kv head", bucket 64
    cache = jnp.pad(latent[None, :, None, :39], ((0, 0),) * 3 + ((0, 25), (0, 0)))
    got = mla.mla_decode_absorbed(
        q[:, -1:], cache, 0, latent[:, -1:], jnp.asarray([39]), 64,
        p["w_kvb"], CFG.kv_lora_rank, CFG.qk_nope_dim, CFG.v_head_dim)
    assert rel(got.reshape(-1) @ p["wo"], want) < TOL


def _moe_call(p, h, held, valid=None):
    idx, w = route_top_k(h, p["router"], p["bias"], CFG.top_k, CFG.route_scale,
                         CFG.renormalize)
    return dropless_moe(h, idx, w, p["w_gate"][jnp.asarray(held)],
                        p["w_up"][jnp.asarray(held)], p["w_down"][jnp.asarray(held)],
                        tuple(held), CFG.n_experts, valid)


def test_skewed_routing_drops_nothing(params):
    """A correction bias that sends every token to expert 5 first: 64 tokens
    on one expert (a capacity of 1.25 x 64 x 2 / 8 = 20 would drop 44)."""
    p = dict(params["layers"][1]["moe"])
    p["bias"] = jnp.zeros((8,)).at[5].set(10.0)
    h = jax.random.normal(jax.random.PRNGKey(8), (64, CFG.d_model))
    y, landed, touched = _moe_call(p, h, list(range(8)))
    assert int(landed) == 128 and int(touched) >= 2
    assert rel(y, ref._moe(h, p, C, held=list(range(8)), shared=False)) < TOL


def test_idle_tokens_are_routed_nowhere(params):
    p = params["layers"][1]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(9), (16, CFG.d_model))
    valid = jnp.arange(16) < 5
    y, landed, _ = _moe_call(p, h, list(range(8)), valid)
    assert int(landed) == 10 and not np.asarray(y[5:]).any()
    assert rel(y[:5], _moe_call(p, h[:5], list(range(8)))[0]) < TOL


@pytest.mark.parametrize("live,tier", [(50, 128), (120, 256), (200, 400)])
def test_every_row_tier_of_the_grouped_product_is_the_layer(params, live, tier):
    """200 tokens x top-2 = 400 rows offered: the product runs at 128, 256
    or all 400 rows by how many assignments landed, and each is the layer."""
    p = params["layers"][1]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(11), (200, CFG.d_model))
    y, landed, _ = _moe_call(p, h, list(range(8)), jnp.arange(200) < live)
    assert int(landed) == 2 * live and (tier == 400 or 2 * live <= tier)
    assert rel(y[:live], ref._moe(h[:live], p, C, held=list(range(8)),
                                  shared=False)) < TOL


@pytest.mark.parametrize("held,skew", [([0, 1], False), ([4, 5], True)])
def test_a_prompt_pass_runs_the_share_that_lands_or_all(params, held, skew):
    """600 tokens x top-2 = 1200 rows offered to a chip that holds 2 of 8
    experts: the products run at 5/4 of the even share + 256 rows (768), or,
    when a bias sends every token to the two held experts, at all 1200; both
    are the layer."""
    from ray_tpu.ops.moe import _row_tiers
    assert _row_tiers(1200, 2, 8) == [768, 1200]
    assert _row_tiers(32768, 64, 256) == [10496, 32768]
    assert _row_tiers(512, 64, 256) == [128, 256, 512]
    p = dict(params["layers"][1]["moe"])
    if skew:
        p["bias"] = jnp.zeros((8,)).at[jnp.asarray(held)].set(10.0)
    h = jax.random.normal(jax.random.PRNGKey(12), (600, CFG.d_model))
    y, landed, _ = _moe_call(p, h, held)
    assert (int(landed) > 768) == skew
    sub = {**p, **{k: p[k][jnp.asarray(held)] for k in ("w_gate", "w_up", "w_down")}}
    assert rel(y, ref._moe(h, sub, C, held=held, shared=False)) < TOL


def test_the_shares_of_four_chips_add_up_to_the_whole_layer(params):
    """Experts split 4 ways (2 each): the four chips' routed parts plus the
    shared expert ONCE = the uncut reference layer."""
    p = params["layers"][3]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(10), (48, CFG.d_model))
    whole = ref._moe(h, p, C, held=list(range(8)), shared=True)
    total, landed = ref._swiglu(h, p["shared"]), 0
    for chip in range(4):
        held = [2 * chip, 2 * chip + 1]
        y, n, _ = _moe_call(p, h, held)
        # each share is also the reference's share
        sub = {**p, **{k: p[k][jnp.asarray(held)] for k in ("w_gate", "w_up", "w_down")}}
        assert rel(y, ref._moe(h, sub, C, held=held, shared=False)) < TOL
        total, landed = total + y, landed + int(n)
    assert landed == 48 * CFG.top_k      # every assignment landed on one chip
    assert rel(total, whole) < TOL


def _greedy_reference(params, prompt, n):
    toks = list(prompt)
    for _ in range(n):
        lg = hybrid.forward(params, jnp.asarray([toks]), CFG)
        toks.append(int(jnp.argmax(lg[0, -1])))
    return toks[len(prompt):]


def test_a_reused_slot_answers_as_if_served_alone(params):
    """One slot, two requests one after the other: the second finds the
    first's recurrent state and latent rows in the slot and must not see
    them (admission replaces the state)."""
    eng = ContinuousBatchingEngine(params, CFG, num_slots=1, max_len=64)
    a = [5, 9, 17, 300, 2, 2, 40, 41, 42, 43, 44]
    b = [7, 7, 3]
    first = eng.generate(a, max_new_tokens=6)
    second = eng.generate(b, max_new_tokens=6)
    alone = ContinuousBatchingEngine(params, CFG, num_slots=1, max_len=64
                                     ).generate(b, max_new_tokens=6)
    assert second == alone and first[:len(a)] == a
    assert second[len(b):] == _greedy_reference(params, b, 6)


@pytest.mark.parametrize("which", ["dense", "hybrid"])
def test_one_engine_class_serves_both_models(params, which):
    """The same class, step loop and spans; the cache is the model's."""
    if which == "dense":
        cfg = ModelConfig.tiny()
        p = init_params(jax.random.PRNGKey(0), cfg)
    else:
        cfg, p = CFG, params
    tracing.clear()
    eng = ContinuousBatchingEngine(p, cfg, num_slots=2, max_len=64)
    ids = [eng.submit([3, 4, 5, 6, 7][:n], max_new_tokens=5) for n in (5, 3, 4)]
    eng.run_until_done()
    for i, n in zip(ids, (5, 3, 4)):
        assert len(eng.result(i)) == n + 5
    steps = [e for e in tracing.get_events() if e["name"] == "engine.step"]
    prefills = [e for e in tracing.get_events() if e["name"] == "engine.prefill"]
    assert steps and len(prefills) == 3
    busy = [e["args"] for e in steps if e["args"].get("active")]
    if which == "dense":
        assert type(eng.cache).__name__ == "DenseKVCache" and eng.k.ndim == 5
        assert not any("state_slots" in a for a in busy)
    else:
        assert type(eng.cache).__name__ == "HybridCache"
        assert all(a["state_slots"] == a["active"] and a["latent_rows"] > 0
                   for a in busy)
        counted = [a for a in busy if "experts_touched" in a]
        # 3 expert layers x 8 held experts; an active slot sends top_k = 2
        # assignments into each layer, an idle slot none
        assert counted and all(0 < a["experts_touched"] <= 24 and
                               a["expert_assignments"] in (6, 12) for a in counted)
        assert all(e["args"]["state_layers"] == 3 and
                   e["args"]["latent_layers"] == 1 for e in prefills)


def test_llm_replica_builds_either_model():
    from ray_tpu.serve.llm import LLMReplica

    for preset in ("tiny", "tiny_hybrid"):
        r = LLMReplica(preset, num_slots=2, max_len=32)
        assert len(r({"prompt": [1, 2, 3], "max_new_tokens": 3})) == 6
