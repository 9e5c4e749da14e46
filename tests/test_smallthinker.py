"""SmallThinker's block on the window form (`HybridConfig.tiny_smallthinker()`:
two periods of full, window x 3 with the global layer FIRST, a window of 8
positions, 14 query heads on 2 key heads (a group of 7), the sequential
RMSNorm block whose router reads the ATTENTION's input, ReGLU experts all
held, no shared expert, half-rotation rotary, an untied head) against the
plain float32 reference of `perfbench/references/smallthinker.py`: the whole
sequence; prefill + decode through `SwaCache` with a prompt of three chunks
and an answer that starts under the window and ends past it; the four swaps
that must not pass unnoticed (route after the attention, silu, positions on a
global layer, the parallel block); 7 query heads a key head through both
kernels' wrappers and through the fallbacks; the share test over 64 experts;
and that bf16 where the configuration says float32 fails the tolerance.

Tolerances, logits as a relative error of the whole row (`ref.rel_err`):
2e-5 for a whole pass and 3e-5 through the slot state, both in float32
against a float32 reference at `highest` precision: what is left is the
order of the sums (the program's softmax and grouped products add in other
orders than the reference's loops: 8e-7 read for the whole pass). The same
stack with its residual stream, norms and router in bf16 reads 5e-2, and
the swaps 0.33 (the rotary layout) to 1.04 (the parallel block); a swap
counts as seen above 1e-3."""

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
from ray_tpu.ops import moe as moe_ops
from ray_tpu.ops.pallas import decode_attention, flash_attention
from ray_tpu.util import tracing

attention_ops = importlib.import_module("ray_tpu.ops.attention")   # the module, not its function

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = HybridConfig.tiny_smallthinker()
W = CFG.swa_window          # 8
LAYOUT = [0, 1, 1, 1] * 2   # the global layer first
# the configuration file of the same model, in the reference's key names
C = {"hidden_size": 64, "num_hidden_layers": 8, "rope_layout": LAYOUT,
     "sliding_window_layout": LAYOUT, "sliding_window_size": 8,
     "num_attention_heads": 14, "num_key_value_heads": 2, "head_dim": 16,
     "rope_theta": 1.5e6, "rope_scaling": None, "rms_norm_eps": 1e-6,
     "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 8,
     "moe_num_active_primary_experts": 3, "moe_primary_router_apply_softmax": True,
     "norm_topk_prob": True, "tie_word_embeddings": False, "vocab_size": 96,
     "experts_held": {"of": 8, "first": 0, "count": 8}}
S = 48     # positions of the test sequences: six windows
PASS, STATE, MOVED = 2e-5, 3e-5, 1e-3


@pytest.fixture(scope="module")
def ref():
    from perfbench.lib.manifest import load_py

    return load_py(os.path.join(ROOT, "perfbench", "references", "smallthinker.py"))


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


def _fresh(cfg, **changes):
    """`cfg` with `changes`, and a static argument no other test has traced
    (a patched function is then traced anew, not found in jit's cache)."""
    _fresh.n = getattr(_fresh, "n", 4096) + 1
    return dataclasses.replace(cfg, prefill_tokens=_fresh.n, **changes)


# -------------------------------------------------------------------- model


def test_the_stack_is_six_runs_with_the_global_layer_first_and_two_norms(params):
    assert CFG.runs() == (("full", 1), ("swa", 3)) * 2
    assert CFG.run_ffns() == ("moe",) * 4 and CFG.windowed and CFG.scanned
    assert not CFG.parallel and not CFG.layer_normed
    full, window = params["runs"][:2]
    assert set(full) == {"mixer_norm", "ffn_norm", "moe", "full"}
    assert set(window) == {"mixer_norm", "ffn_norm", "moe", "swa"}
    assert set(window["moe"]) == {"router", "w_gate", "w_up", "w_down"}   # no shared expert
    assert window["swa"]["wq"].shape == (3, 64, 14 * 16)
    assert params["lm_head"].shape == (64, 96)       # an untied head
    assert isinstance(CFG.make_cache(2, 64), hybrid.SwaCache)
    for bad in ({"swa_block": "both"}, {"swa_norm": "batch"}, {"swa_rotary": "pairs"},
                {"route_from": "embedding"},
                {"swa_block": "parallel"}):          # whose route reads its ONE norm
        with pytest.raises(ValueError, match="the window form's block"):
            dataclasses.replace(CFG, **bad).runs()
    with pytest.raises(ValueError, match="window .swa. and full attention mixers"):
        dataclasses.replace(CFG, router="argmax").runs()
    # Command A+'s block is every default
    cmda = HybridConfig.tiny_swa()
    assert cmda.parallel and cmda.layer_normed and cmda.gate_act == "silu" \
        and cmda.swa_rotary == "interleaved" and cmda.route_from == "ffn"
    assert not HybridConfig.tiny_granite().parallel


def test_whole_sequence_logits(ref, params, tokens, want):
    assert _rel(ref, hybrid.forward(params, tokens, CFG), want) < PASS


@pytest.mark.parametrize("swap", [
    {"route_from": "ffn"},                            # the route read behind the attention
    {"gate_act": "silu"},                             # SwiGLU experts
    {"swa_block": "parallel", "route_from": "ffn"},   # Command A+'s block order
    {"swa_rotary": "interleaved"},
    {"swa_norm": "layer"},
])
def test_a_swapped_property_moves_the_logits(ref, params, tokens, want, swap):
    got = hybrid.forward(params, tokens, dataclasses.replace(CFG, **swap))
    assert _rel(ref, got, want) > MOVED, swap


def test_positions_on_a_global_layer_move_the_logits(monkeypatch, ref, params, tokens, want):
    """Inside one window (8 positions, ONE chunk) the band never binds, so the
    only thing a global layer lacks is the rotation: give it one."""
    real = hybrid._swa_qkv
    monkeypatch.setattr(hybrid, "_swa_qkv", lambda cfg, a, h, positions: real(
        cfg, a, h, jnp.arange(h.shape[-2]) if positions is None else positions))
    got = hybrid.forward(params, tokens[:, :W], _fresh(CFG))
    assert _rel(ref, got, want[:, :W]) > MOVED
    monkeypatch.undo()
    assert _rel(ref, hybrid.forward(params, tokens[:, :W], _fresh(CFG)), want[:, :W]) < PASS


def test_bfloat16_where_the_configuration_says_float32_fails(monkeypatch, ref, params,
                                                             tokens, want):
    """The residual stream, the norms' rows and the router's input in bf16
    (weights untouched): a hundred times the tolerance."""
    monkeypatch.setattr(hybrid, "F32", jnp.bfloat16)
    got = hybrid.forward(params, tokens, _fresh(CFG))
    assert _rel(ref, got.astype(jnp.float32), want) > 100 * PASS


def test_a_chunk_of_several_expert_blocks_carries_its_route(monkeypatch, ref, params, tokens):
    """The cell's chunk of 4,096 positions goes through the expert layer in
    blocks of 2,048 (`_FFN_BLOCK`): the route made ahead, from the attention's
    input, is cut into the same blocks. Here a window of 16 in blocks of 8."""
    monkeypatch.setattr(hybrid, "_FFN_BLOCK", 8)
    got = hybrid.forward(params, tokens[:1], _fresh(CFG, swa_window=16))
    want = ref.logits(params, tokens[:1], {**C, "sliding_window_size": 16})
    assert _rel(ref, got, want) < PASS


def test_a_weight_left_out_changes_the_logits(ref, params, tokens, want):
    for leaf in [("mixer_norm",), ("ffn_norm",), ("full", "wo"), ("moe", "router"),
                 ("moe", "w_up")]:
        def ones(tree, path):
            if len(path) == 1:
                return {**tree, path[0]: jnp.ones_like(tree[path[0]])}
            return {**tree, path[0]: ones(tree[path[0]], path[1:])}
        changed = {**params, "runs": [ones(params["runs"][0], leaf)] + params["runs"][1:]}
        assert _rel(ref, hybrid.forward(changed, tokens, CFG), want) > MOVED, leaf


@pytest.mark.parametrize("chunks", [1, 2, 5])
def test_a_prompt_pass_of_whole_chunks_is_the_reference(ref, params, tokens, want, chunks):
    """The prompt pass walks `chunks` chunks of one window through SIX runs (a
    loop whose trip count is data), the route of every layer made from the
    attention's input and used behind it, and gives the logits at the last
    true position; the rows it leaves carry a decoded position too."""
    cache = CFG.make_cache(2, 64)
    n = chunks * W - 3                       # true length: the last chunk is part padding
    bucket = cache.prompt_bucket(n)
    assert bucket == (W if chunks == 1 else 64)
    row = np.zeros((1, bucket), np.int32)
    row[0, :n] = np.asarray(tokens[0, :n])
    lens = jnp.asarray([n], jnp.int32)
    logits, rows = hybrid.prefill(params, jnp.asarray(row), lens, CFG, with_routing=True)
    assert _rel(ref, logits[0], want[0, n - 1]) < PASS
    assert rows["k"].shape == (2, 1, 2, bucket, 16) and rows["wk"].shape == (6, 1, 2, W, 16)
    # the experts every position chose, in the stack's order (global first)
    assert rows.pop("routing").shape == (8, 1, bucket, 3)
    lengths, held = cache.write(jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
                                jnp.asarray([1]), rows, lens, jnp.asarray([0]))
    tok = jnp.zeros((2,), jnp.int32).at[1].set(tokens[0, n])
    _, stepped, _ = hybrid.decode_logits(params, cache.state, lengths, tok, None, CFG, 64)
    assert _rel(ref, stepped[1], want[0, n]) < STATE


def test_prefill_then_decode_wraps_the_ring_while_decoding(ref, params, tokens, want):
    """A prompt of 21 positions (three chunks: the ring wrapped twice in the
    pass) and one of 5 (under the window), then 27 decoded positions through
    the slot state: the second answer starts under the window, its ring wraps
    at its fourth step and the row at n mod 8 leaves at every step after;
    every position's logits against the reference's whole forward."""
    cache = CFG.make_cache(4, 64)
    lengths, held = jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32)
    for b, (n, slot) in enumerate([(21, 2), (5, 0)]):
        row = np.zeros((1, cache.prompt_bucket(n)), np.int32)
        row[0, :n] = np.asarray(tokens[b, :n])
        lens = jnp.asarray([n], jnp.int32)
        logits, rows = hybrid.prefill(params, jnp.asarray(row), lens, CFG)
        assert _rel(ref, logits[0], want[b, n - 1]) < PASS
        lengths, held = cache.write(lengths, held, jnp.asarray([slot]), rows, lens,
                                    jnp.asarray([0]))
    state = cache.state
    for t in range(27):
        tok = np.zeros((4,), np.int32)
        tok[2], tok[0] = int(tokens[0, 21 + t]), int(tokens[1, 5 + t])
        assert cache.step_args([int(n) for n in lengths if n], 64)["wrapped_slots"] == \
            1 + (5 + t >= W)
        state, logits, routing = hybrid.decode_logits(
            params, state, lengths, jnp.asarray(tok), None, CFG, 64)
        lengths = lengths + (lengths > 0)
        assert routing.shape == (8, 4, 3)
        assert _rel(ref, logits[2], want[0, 21 + t]) < STATE, t
        assert _rel(ref, logits[0], want[1, 5 + t]) < STATE, t
    assert lengths.tolist() == [32, 0, 48, 0]


def test_the_reference_follows_a_forced_route(ref, params, tokens, want):
    """What the benchmark's comparison does: the reference under the PROGRAM's
    choice of experts gives its own logits where the two agree, and reports
    no margin."""
    row = jnp.asarray(np.asarray(tokens[:1, :24]))
    _, rows = hybrid.prefill(params, jnp.pad(row, ((0, 0), (0, 40))), jnp.asarray([24]),
                             CFG, with_routing=True)
    got, worst = ref.logits_routed(params, row, C, rows["routing"][:, :, :24])
    assert _rel(ref, got, want[:1, :24]) < 1e-6 and float(worst) < 1e-5


def test_short_and_long_requests_through_the_engine(ref, params):
    """Prompts inside one window and of three to five windows staggered over
    two slots answer as the reference's greedy continuation; the steps' spans
    count the slots whose ring has wrapped."""
    tracing.clear()
    eng = ContinuousBatchingEngine(params, CFG, num_slots=2, max_len=64)
    prompts = [list(range(3, 3 + n)) for n in (5, 37, 8, 21, 3)]
    ids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.run_until_done()
    for rid, prompt in zip(ids, prompts):
        got = eng.result(rid)[len(prompt):]
        assert len(got) == 6
        logits = ref.logits(params, jnp.asarray([prompt + got[:-1]]), C)[0, len(prompt) - 1:]
        # every answered token is the reference's own greedy choice
        assert float(jnp.max(jnp.max(logits, -1)
                             - logits[jnp.arange(6), jnp.asarray(got)])) < 1e-4
    steps = [e["args"] for e in tracing.get_events()
             if e["name"] == "engine.step" and "window_rows" in e.get("args", {})]
    assert steps and all(0 <= s["wrapped_slots"] <= s["active"] for s in steps)
    assert any(s["wrapped_slots"] for s in steps) \
        and any(s["wrapped_slots"] < s["active"] for s in steps)
    assert all(s["experts_touched"] <= 8 * 8 for s in steps if "experts_touched" in s)
    # every expert is held: every assignment of a busy slot lands
    assert all(s["expert_assignments"] == 8 * 3 * s["active"] for s in steps
               if "expert_assignments" in s and not s.get("prefill_batches"))
    dispatched = [e["args"] for e in tracing.get_events()
                  if e["name"] == "engine.prefill_dispatch"]
    assert sorted(a["tokens"] for a in dispatched) == [3, 5, 8, 21, 37]
    assert sorted(a["bucket"] for a in dispatched) == [8, 8, 8, 64, 64]
    passes = [e["args"] for e in tracing.get_events() if e["name"] == "engine.prefill"]
    assert all((a["window_layers"], a["full_layers"], a["chunk"]) == (6, 2, W)
               for a in passes)
    tracing.clear()


def test_the_route_is_named_before_the_attention_and_the_experts_behind(params):
    """`moe/route_ahead`, then `swa/full` | `swa/window`, then `moe/experts`:
    a trace says what lies between the route and its use."""
    text = hybrid.decode_step.lower(
        params, CFG.make_cache(2, 64).state, jnp.zeros((2,), jnp.int32),
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), bool), CFG, 64).as_text(debug_info=True)
    for scope in ("moe/route_ahead", "swa/window", "swa/full", "moe/experts",
                  "state_write", "head"):
        assert scope in text, scope
    assert "shared_expert" not in text
    cmda = HybridConfig.tiny_swa()
    other = hybrid.decode_step.lower(
        hybrid.init_params(jax.random.PRNGKey(0), cmda), cmda.make_cache(2, 64).state,
        jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32), jnp.zeros((2,), bool),
        cmda, 64).as_text(debug_info=True)
    assert "route_ahead" not in other and "moe/experts" not in other


# ------------------------------------------------------------------ pieces


def test_the_gate_is_the_callers(monkeypatch, ref):
    """`dropless_moe` with `gate_act` "relu" is the reference's ReGLU loop, in
    both of its tiers (the grouped products and, past `_GATHERED_BYTES`, every
    expert over all tokens); "silu" is what it was; another name is refused."""
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    T, d, f, E, k = 24, 64, 32, 8, 3
    x = jax.random.normal(ks[0], (T, d))
    gate, up = (jax.random.normal(kk, (E, d, f)) * d ** -0.5 for kk in ks[1:3])
    down = jax.random.normal(ks[3], (E, f, d)) * f ** -0.5
    idx, w = moe_ops.route_softmax_top_k(x, jax.random.normal(ks[4], (d, E)), k)
    dense = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], idx].set(w)
    with jax.default_matmul_precision("highest"):
        want = ref.moe(x, dense, {"w_gate": gate, "w_up": up, "w_down": down},
                       list(range(E)))
        got, landed, touched = moe_ops.dropless_moe(
            x, idx, w, gate, up, down, tuple(range(E)), E, gate_act="relu")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
        assert int(landed) == T * k and int(touched) == E
        silu, _, _ = moe_ops.dropless_moe(x, idx, w, gate, up, down, tuple(range(E)), E)
        assert float(jnp.abs(silu - want).max()) > 1e-2
        monkeypatch.setattr(moe_ops, "_GATHERED_BYTES", 0)   # the last tier: every expert
        every, _, _ = moe_ops.dropless_moe(
            x, idx, w, gate, up, down, tuple(range(E)), E,
            valid=jnp.ones((T,), bool), gate_act="relu")
        np.testing.assert_allclose(np.asarray(every), np.asarray(want), atol=2e-5)
    with pytest.raises(KeyError):
        moe_ops.dropless_moe(x, idx, w, gate, up, down, tuple(range(E)), E,
                             gate_act="gelu")


@pytest.mark.parametrize("fields", [
    {"gate_act": "gelu"},                      # no gate the experts know
    {"gate_act": "relu", "n_shared": 1},       # a shared MLP is SwiGLU
    {"gate_act": "relu", "swa_layers": (), "full_layers": ()},   # no window form
], ids=["gelu", "relu-with-shared", "relu-not-windowed"])
def test_a_gate_the_block_cannot_have_is_refused_before_any_trace(fields):
    """The gate's activation is checked where the block's other properties
    are, in the configuration: at its making, or when its runs are asked for,
    and not inside a layer's body while a program is traced."""
    with pytest.raises(ValueError, match="gate|SwiGLU"):
        dataclasses.replace(CFG, **fields).runs()


def test_the_shares_of_64_experts_add_up_to_the_uncut_layer(ref):
    """The guide's share test: four chips each hold 16 of the 64 experts. The
    four held parts are the uncut expert layer, in the reference and in the
    program's layer told which experts it holds, under a route made from
    OTHER rows than the experts read (the attention's input)."""
    cfg = dataclasses.replace(CFG, n_experts=64, top_k=6, d_expert=16,
                              experts_held=tuple(range(64)))
    c = {**C, "moe_num_primary_experts": 64, "moe_num_active_primary_experts": 6}
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    h, u = jax.random.normal(ks[0], (24, 64)), jax.random.normal(ks[1], (24, 64))
    m = {"router": jax.random.normal(ks[2], (64, 64)) * 0.125,
         "w_gate": jax.random.normal(ks[3], (64, 64, 16)) * 0.125,
         "w_up": jax.random.normal(ks[4], (64, 64, 16)) * 0.125,
         "w_down": jax.random.normal(ks[5], (64, 16, 64)) * 0.25}
    with jax.default_matmul_precision("highest"):
        weights, _ = ref.moe_weights(h, m["router"], c)
        whole = ref.moe(u, weights, m, list(range(64)))
        route = hybrid._route(cfg, m, h)
        uncut, landed, touched, _ = hybrid._ffn(cfg, {"moe": m}, u, u, jnp.ones((24,), bool),
                                                route=route)
        np.testing.assert_allclose(np.asarray(uncut), np.asarray(whole), atol=2e-5)
        assert int(landed) == 24 * 6
        parts = []
        for first in range(0, 64, 16):
            held = list(range(first, first + 16))
            mine = {**m, **{n: m[n][first:first + 16] for n in ("w_gate", "w_up", "w_down")}}
            part = ref.moe(u, weights, mine, held)
            parts.append(part)
            got, landed, touched, _ = hybrid._ffn(
                dataclasses.replace(cfg, experts_held=tuple(held)), {"moe": mine}, u, u,
                jnp.ones((24,), bool), route=route)
            np.testing.assert_allclose(np.asarray(got), np.asarray(part), atol=2e-5)
            assert int(touched) <= 16 and int(landed) == int((weights[:, held] > 0).sum())
        np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole), atol=2e-5)
    assert min(float(jnp.abs(p).max()) for p in parts) > 1e-3


def test_the_route_ahead_is_the_mixer_inputs(ref, params, tokens):
    """A one-layer stack's hidden change is the reference's: the experts'
    weights from h = RMSNorm(x), their inputs u = RMSNorm(x + A(h))."""
    one = dataclasses.replace(CFG, n_layers=1, swa_layers=(), full_layers=(1,))
    p1 = {**params, "runs": params["runs"][:1]}
    toks = tokens[:1, :24]
    x, _, _ = hybrid._sequence_swa(p1, toks, jnp.asarray([24]), one)
    lp = jax.tree_util.tree_map(lambda a: a[0], p1["runs"][0])
    x0 = params["embed"][toks[0]].astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        h = ref._rms_norm(x0, lp["mixer_norm"], 1e-6)
        x1 = x0 + ref.attention(h, lp["full"], "full", {**C, "num_hidden_layers": 1})
        u = ref._rms_norm(x1, lp["ffn_norm"], 1e-6)
        ahead, _ = ref.moe_weights(h, lp["moe"]["router"], C)
        behind, _ = ref.moe_weights(u, lp["moe"]["router"], C)
        f = ref.moe(u, ahead, lp["moe"], list(range(8)))
    np.testing.assert_allclose(np.asarray(x[0]), np.asarray(x1 + f), atol=2e-5)
    assert float(jnp.mean((ahead > 0) != (behind > 0))) > 0.05   # another choice of experts


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


@pytest.mark.parametrize("window,q_start,k_lo", [(256, 256, 0), (None, 256, 0)])
def test_the_banded_flash_kernel_groups_seven_heads(monkeypatch, window, q_start, k_lo):
    """`flash_attention_banded` (interpret mode) at 14 query heads on 2 key
    heads: query head j reads key head j // 7 through the index map."""
    monkeypatch.setattr(attention_ops, "uses_flash_kernel", lambda q: True)
    b, H, kvh, sq, d, sk = 1, 14, 2, 256, 128, 512
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, H, sq, d))
    k, v = (jax.random.normal(kk, (2, b, kvh, sk, d)) for kk in ks[1:])
    real = flash_attention.flash_attention_banded
    monkeypatch.setattr(flash_attention, "flash_attention_banded",
                        lambda *a, **kw: real(*a, **kw, block_q=128, block_k=128))
    got = attention_ops.banded_attention(
        q, k, v, jnp.asarray(1), jnp.asarray(q_start), jnp.asarray(k_lo),
        window=window, sm_scale=0.09)
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        _masked(q, k, v, 1, q_start, k_lo, window, 0.09)), atol=2e-5, rtol=2e-5)


def test_banded_attention_without_the_kernel_groups_seven_heads():
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, 14, 16, 16))
    k, v = (jax.random.normal(kk, (3, 2, 2, 40, 16)) for kk in ks[1:])
    for window, q_start, k_lo in [(8, 16, 0), (None, 24, 0)]:
        got = attention_ops.banded_attention(q, k, v, 2, q_start, k_lo, window=window,
                                             sm_scale=0.25)
        np.testing.assert_allclose(np.asarray(got), np.asarray(
            _masked(q, k, v, 2, q_start, k_lo, window, 0.25)), atol=1e-5)


RAGGED = [0, 1, 31, 33, 127, 128, 129, 200, 1000]


@pytest.mark.parametrize("ring", [True, False])
def test_the_decode_kernel_takes_a_group_of_seven(monkeypatch, ring):
    """`gqa_decode_attention` (interpret mode) at 7 query heads a key head:
    the wrapper pads the group to 8 sublanes and gives 7 back, over a ring
    across its wrap (`skip`) and over plain rows; against the masked einsum,
    which is also the step's fallback."""
    monkeypatch.setattr(decode_attention, "_BLOCK_ROWS", 32)
    Wr, B, kvh, rep, hd = 128, len(RAGGED), 2, 7, 128
    n = jnp.asarray(RAGGED, jnp.int32)
    held = jnp.minimum(n, Wr)
    skip = jnp.where(n >= Wr, n % Wr, -1) if ring else jnp.full_like(n, -1)
    ks = jax.random.split(jax.random.PRNGKey(2), 5)
    q = jax.random.normal(ks[0], (B, kvh, rep, hd))
    kc, vc = (jax.random.normal(k, (B, kvh, hd)) for k in ks[1:3])
    k_all, v_all = (jax.random.normal(k, (2, B, kvh, Wr, hd)) for k in ks[3:])
    rows = jnp.arange(Wr)[None, :]
    live = (rows < held[:, None]) & (rows != skip[:, None])
    want = _gqa_decode_attention(q.reshape(B, kvh * rep, 1, hd), k_all[1], v_all[1],
                                 kc, vc, live, 0.09)
    dead = lambda a: jnp.where(live[None, :, None, :, None], a, jnp.nan)
    got = decode_attention.gqa_decode_attention(
        q, kc, vc, dead(k_all), dead(v_all), jnp.asarray(1),
        decode_attention.live_items(held, Wr), Wr, 0.09, skip=skip if ring else None)
    assert got.shape == (B, kvh, rep, hd)
    np.testing.assert_allclose(np.asarray(got.reshape(B, kvh * rep, hd)),
                               np.asarray(want), atol=2e-5, rtol=2e-5)
