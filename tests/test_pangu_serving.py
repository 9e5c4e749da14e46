"""The rotary-MLA / sandwich-norm / prediction-module configuration of the
hybrid model (openPangu-Ultra-MoE's layer kinds) against the plain float32
reference `perfbench/references/openpangu_ultra_moe.py`, at a toy size:
dense layer + 2 expert layers, 8 heads, a low-rank query, 8 experts top-2
with one shared, one prediction module, a vocabulary of 16; float32, seeded
random weights.

Tolerances as `tests/test_hybrid_model.py` sets them: program and reference
order their sums differently (absorbed against expanded, sorted groups
against a loop over experts), so they agree to a few float32 roundings:
1e-4 is 40 times under what one bf16 rounding of any operand gives."""

import collections
import dataclasses
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import hybrid
from ray_tpu.models.serving import ContinuousBatchingEngine
from ray_tpu.ops import mla
from ray_tpu.ops.moe import dropless_moe, route_top_k
from ray_tpu.ops.pallas import mla_decode
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "openpangu_reference",
    os.path.join(ROOT, "perfbench", "references", "openpangu_ultra_moe.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

TOL = 1e-4
CFG = hybrid.HybridConfig.tiny_rotary()
# the same toy in the configuration file's key names, for the reference
C = {"hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
     "num_hidden_layers": 3, "num_attention_heads": 8, "kv_lora_rank": 32,
     "q_lora_rank": 24, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
     "v_head_dim": 16, "rope_theta": 1e4, "n_routed_experts": 8,
     "num_experts_per_tok": 2, "first_k_dense_replace": 1,
     "routed_scaling_factor": 2.5, "norm_topk_prob": True,
     "rms_norm_eps": 1e-5, "vocab_size": 16,
     "experts_held": {"of": 8, "first": 0, "count": 8}}


def rel(got, want):
    return float(ref.rel_err(jnp.asarray(got), jnp.asarray(want)))


@pytest.fixture(scope="module")
def params():
    p = hybrid.init_params(jax.random.PRNGKey(0), CFG)
    # norm weights off 1, so that a norm left out or misplaced shows
    keys = iter(jax.random.split(jax.random.PRNGKey(9), 64))

    def off_one(path, w):
        if w.ndim == 1 and "norm" in str(path[-1]):
            return w + 0.2 * jax.random.normal(next(keys), w.shape)
        return w

    return jax.tree_util.tree_map_with_path(off_one, p)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 48), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def want(params, tokens):
    main, module, _ = ref.logits_routed(params, tokens, C)
    return np.asarray(main), np.asarray(module)


def test_forward_is_the_reference(params, tokens, want):
    main, module = hybrid.forward(params, tokens, CFG, with_mtp=True)
    assert rel(main, want[0]) < TOL
    assert rel(module, want[1][:, :-1]) < TOL
    assert rel(hybrid.forward(params, tokens, CFG), want[0]) < TOL


def test_prefill_feeds_the_module_the_first_token_it_is_given(params, tokens, want):
    """A teacher-forced comparison hands the prompt pass the token that
    FOLLOWED each prompt (`first`): the module's last row, its logits and
    the draft are then the reference's over the sequence as it is, whatever
    the model itself would have answered; the main logits do not move."""
    lens = [19, 30]
    prompt = np.zeros((2, 32), np.int32)
    for j, n in enumerate(lens):
        prompt[j, :n] = np.asarray(tokens[j, :n])
    lens_d = jnp.asarray(lens, jnp.int32)
    given = jnp.asarray([int(tokens[j, n]) for j, n in enumerate(lens)], jnp.int32)
    logits, rows = hybrid.prefill(params, jnp.asarray(prompt), lens_d, CFG,
                                  with_routing=True, first=given)
    own, own_rows = hybrid.prefill(params, jnp.asarray(prompt), lens_d, CFG,
                                   with_routing=True)
    assert np.array_equal(np.asarray(logits), np.asarray(own))
    assert any(int(jnp.argmax(own[j])) != int(given[j]) for j in range(2))
    for j, n in enumerate(lens):
        assert rel(rows["mtp_logits"][j], want[1][j, n - 1]) < TOL
        assert int(rows["draft"][j]) == int(np.argmax(want[1][j, n - 1]))
    assert not np.array_equal(np.asarray(rows["latent"][-1]),
                              np.asarray(own_rows["latent"][-1]))


# how many positions each teacher-forced step keeps: a draft that holds (2),
# one that is refused (1: its row stays behind and is overwritten)
@pytest.mark.parametrize("keeps", [(2, 2, 2, 2), (1, 1, 1, 1, 1, 1),
                                   (2, 1, 1, 2, 1, 2)],
                         ids=["accepted", "refused", "mixed"])
def test_prefill_then_decode_through_the_slots_is_the_reference(
        params, tokens, want, keeps):
    """Prompts of 19 and 30 tokens in one prompt pass of 2 x 32, written
    into slots 4 and 1 of 6; then two positions a step, teacher-forced,
    through the donated slot state, `lengths` advanced by 1 or 2: the main
    logits and the module's of every position against the reference's full
    forward, whatever was kept before. The rotary positions run on across
    the boundary and behind a refused draft."""
    lens, at, slots = [19, 30], [4, 1], 6
    cache = CFG.make_cache(slots, 64)
    prompt = np.zeros((2, 32), np.int32)
    for j, n in enumerate(lens):
        prompt[j, :n] = np.asarray(tokens[j, :n])
    lens_d = jnp.asarray(lens, jnp.int32)
    logits, rows = hybrid.prefill(params, jnp.asarray(prompt), lens_d, CFG,
                                  with_routing=True)
    rows.pop("routing")
    module = rows.pop("mtp_logits")
    for j, n in enumerate(lens):
        assert rel(logits[j], want[0][j, n - 1]) < TOL
    # the module's last row has seen the model's own first token behind the prompt
    for j, n in enumerate(lens):
        seq = np.asarray(tokens[j:j + 1, :n + 1]).copy()
        seq[0, n] = int(jnp.argmax(logits[j]))
        _, m, _ = ref.logits_routed(params, jnp.asarray(seq), C)
        assert rel(module[j], m[0, n - 1]) < TOL
        assert int(rows["draft"][j]) == int(jnp.argmax(m[0, n - 1]))
    lengths, _ = cache.write(
        jnp.zeros((slots,), jnp.int32), jnp.zeros((slots,), jnp.int32),
        jnp.asarray(at, jnp.int32), rows, lens_d, jnp.zeros((2,), jnp.int32))
    # the prompt's last module row was fed the model's choice, not the
    # sequence's token: the first step below writes it again, as a step does
    lengths = lengths - jnp.asarray([0, 1, 0, 0, 1, 0])
    pos = [n - 1 for n in lens]
    active = np.zeros((slots,), bool)
    active[at] = True
    for keep in keeps:
        toks = np.zeros((slots, 2), np.int32)
        nxt = np.zeros((slots, 2), np.int32)
        for j in range(2):
            toks[at[j]] = np.asarray(tokens[j, pos[j]:pos[j] + 2])
            nxt[at[j]] = np.asarray(tokens[j, pos[j] + 1:pos[j] + 3])
        cache.state, main, module, _ = hybrid.verify_logits(
            params, cache.state, lengths, jnp.asarray(toks), jnp.asarray(nxt),
            jnp.asarray(active), CFG, 64)
        for j in range(2):
            for a in range(2):
                assert rel(main[at[j], a], want[0][j, pos[j] + a]) < TOL
                assert rel(module[at[j], a], want[1][j, pos[j] + a]) < TOL
            pos[j] += keep
        lengths = lengths + keep * jnp.asarray(active)


def test_the_modules_layer_calls_the_main_layers_bodies(params):
    """Three main layers and the prediction module's call ONE private
    `_mla_step` (`_mla_seq` in the prompt pass): the layer's index into the
    latent table is data. Their FFN halves are two `_ffn_rows` (the dense
    layer's; experts + shared, the module's too). A second verify step that
    differs in `attn_len` alone traces the MLA step and nothing else."""
    def bodies(lowered):   # {body: (private functions, calls)}, `name_<n>` a second
        text = lowered.as_text()
        kind = r"@(_mla_seq|_mla_step|_ffn_rows)(?:_\d+)?\("
        defs = collections.Counter(re.findall(r"func\.func private " + kind, text))
        calls = collections.Counter(re.findall(r"call " + kind, text))
        return {k: (defs[k], calls[k]) for k in defs}

    def traced_anew():     # on the newest `xla.compile` span
        a = [e["args"] for e in tracing.get_events() if e["name"] == "xla.compile"][-1]
        return a["layers"], a["layer_bodies_traced"]

    jax.clear_caches()   # a body another test traced at these shapes is a hit
    tracing.record_compiles()
    i4 = jax.ShapeDtypeStruct((4,), jnp.int32)
    state = CFG.make_cache(4, 64).state
    step = lambda attn_len: hybrid.decode_step.lower(
        params, state, i4, i4, jax.ShapeDtypeStruct((4,), jnp.bool_), CFG, attn_len)
    assert bodies(step(32)) == {"_mla_step": (1, 4), "_ffn_rows": (2, 4)}
    assert traced_anew() == (4, 3)
    assert bodies(step(64)) == {"_mla_step": (1, 4), "_ffn_rows": (2, 4)}
    assert traced_anew() == (4, 1)
    prompt = hybrid._prefill_first.lower(
        params, jax.ShapeDtypeStruct((2, 16), jnp.int32),
        jax.ShapeDtypeStruct((2,), jnp.int32), CFG)
    assert bodies(prompt) == {"_mla_seq": (1, 4), "_ffn_rows": (2, 4)}
    assert traced_anew() == (4, 3)


def _generate(params, cfg, prompts):
    eng = ContinuousBatchingEngine(params, cfg, num_slots=4, max_len=128)
    ids = [eng.submit(p, max_new_tokens=9 + 2 * i) for i, p in enumerate(prompts)]
    eng.run_until_done()
    return [eng.result(i)[len(p):] for i, p in zip(ids, prompts)], eng


def test_drafting_is_lossless(params):
    """Ten requests of mixed lengths through four slots: the tokens each
    receives with the prediction module drafting are exactly those of the
    same engine with drafting off, and exactly a greedy loop over `forward`;
    with a vocabulary of 16 drafts are both kept and refused."""
    from ray_tpu.util import tracing

    rng = np.random.default_rng(3)
    prompts = [[int(t) for t in rng.integers(0, CFG.vocab_size, n)]
               for n in (5, 9, 17, 3, 12, 30, 7, 21, 11, 26)]
    tracing.clear()
    on, _ = _generate(params, CFG, prompts)
    steps = [e["args"] for e in tracing.get_events() if e["name"] == "engine.step"]
    tracing.clear()
    off, _ = _generate(params, dataclasses.replace(CFG, n_predict=0), prompts)
    assert on == off
    assert [len(a) for a in on] == [9 + 2 * i for i in range(len(prompts))]
    seq = list(prompts[2])
    for _ in range(len(on[2])):
        seq.append(int(jnp.argmax(hybrid.forward(params, jnp.asarray([seq]), CFG)[0, -1])))
    assert seq[len(prompts[2]):] == on[2]
    proposed = sum(a.get("draft_proposed", 0) for a in steps)
    accepted = sum(a.get("draft_accepted", 0) for a in steps)
    assert 0 < accepted < proposed
    # every token a request received was counted in some step's span
    assert sum(a["tokens_out"] for a in steps) == sum(len(a) for a in on)


@pytest.mark.parametrize("n_query", [1, 2])
def test_absorbed_decode_is_the_expanded_form(params, n_query):
    """`n_query` new positions of one slot against its latent rows (the key
    half of W_kvb folded into the query, causal among the new ones) = the
    reference's expanded attention at those positions."""
    p = params["layers"][1]["mla"]
    h = jax.random.normal(jax.random.PRNGKey(7), (1, 40, CFG.d_model))
    want = ref._mla(h, p, C, q_block=40)[0, 40 - n_query:]
    q, latent = hybrid._mla_latent(CFG, p, h, jnp.arange(40))
    old = 40 - n_query
    cache = jnp.pad(latent[None, :, None, :old], ((0, 0),) * 3 + ((0, 64 - old), (0, 0)))
    got = mla.mla_decode_absorbed(
        q[:, old:], cache, 0, latent[:, old:], jnp.asarray([old]), 64,
        p["w_kvb"], CFG.kv_lora_rank, CFG.qk_nope_dim, CFG.v_head_dim)
    assert rel(got.reshape(n_query, -1) @ p["wo"], want) < TOL


@pytest.mark.parametrize("n_query", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_latent_decode_kernel_is_the_einsum_form(monkeypatch, n_query, dtype):
    """The live-rows kernel (interpret mode) against the einsums of
    `mla_decode_absorbed`, in a cache of 3 layers x 5 slots x 128 rows of
    128 lanes, blocks of 32 rows: full, partial and no blocks, an idle slot."""
    monkeypatch.setattr(mla_decode, "_BLOCK_ROWS", 32)
    L, B, H, W, rank, dn, dr, dv = 3, 5, 8, 128, 64, 16, 8, 16
    ks = jax.random.split(jax.random.PRNGKey(n_query), 4)
    normal = lambda k, dims: jax.random.normal(k, dims, jnp.float32).astype(dtype)
    q = normal(ks[0], (B, n_query, H, dn + dr))
    cur = normal(ks[1], (B, n_query, W))
    cache = normal(ks[2], (L, B, 1, 128, W))
    w_kvb = normal(ks[3], (rank, H * (dn + dv))) * rank ** -0.5
    lengths = jnp.asarray([64, 0, 37, 128, 5], jnp.int32)
    args = (q, cache, 1, cur, lengths, 128, w_kvb, rank, dn, dv)
    want = mla.mla_decode_absorbed(*args)
    got = mla.mla_decode_absorbed(*args, mla_decode.live_blocks(lengths, 128))
    assert got.shape == want.shape == (B, n_query, H, dv)
    assert rel(got, want) < (2e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_the_shares_add_up_to_the_whole_expert_layer(params):
    """A router over 64 experts, 32 chips holding 2 each: the 32 shares'
    routed parts, with the shared expert counted once, add up to what the
    uncut reference gives for the whole layer."""
    cfg = dataclasses.replace(CFG, n_experts=64, experts_held=tuple(range(64)))
    c = {**C, "experts_held": {"of": 64, "first": 0, "count": 64}}
    p = hybrid.init_params(jax.random.PRNGKey(4), cfg)["layers"][1]["moe"]
    h = jax.random.normal(jax.random.PRNGKey(5), (24, cfg.d_model))
    idx, w = route_top_k(h, p["router"], p["bias"], cfg.top_k, cfg.route_scale,
                         cfg.renormalize)
    total = ref._swiglu(h, p["shared"])
    for share in range(32):
        held = (2 * share, 2 * share + 1)
        y, _, _ = dropless_moe(h, idx, w, p["w_gate"][jnp.asarray(held)],
                               p["w_up"][jnp.asarray(held)],
                               p["w_down"][jnp.asarray(held)], held, 64)
        total = total + y
    assert rel(total, ref._moe(h, p, c)) < TOL


def test_a_skewed_prompt_pass_drops_nothing_without_gathering(monkeypatch, params):
    """Past `_GATHERED_BYTES` the last tier runs every held expert over all
    tokens: a bias that sends every token to expert 5 first lands them all."""
    from ray_tpu.ops import moe

    monkeypatch.setattr(moe, "_GATHERED_BYTES", 1024)
    p = dict(params["layers"][1]["moe"])
    p["bias"] = jnp.zeros((8,)).at[5].set(10.0)
    h = jax.random.normal(jax.random.PRNGKey(8), (1200, CFG.d_model))
    idx, w = route_top_k(h, p["router"], p["bias"], CFG.top_k, CFG.route_scale,
                         CFG.renormalize)
    y, landed, _ = dropless_moe(h, idx, w, p["w_gate"], p["w_up"], p["w_down"],
                                tuple(range(8)), 8)
    assert int(landed) == 2400
    assert rel(y, ref._moe(h, p, C, shared=False)) < TOL
    few, _, _ = dropless_moe(h, idx, w, p["w_gate"][:2], p["w_up"][:2],
                             p["w_down"][:2], (0, 1), 8)
    two = {**p, **{k: p[k][:2] for k in ("w_gate", "w_up", "w_down")}}
    assert rel(few, ref._moe(h, two, C, held=[0, 1], shared=False)) < TOL
