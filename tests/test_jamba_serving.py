"""The runs form of the hybrid family (Mamba-1 and plain attention mixers,
held and run as scanned runs of like layers) against the plain float32
reference `perfbench/references/jamba.py`, at a toy of the benchmark's
pattern: Mamba x 2, attention, Mamba x 3, attention, Mamba; float32, seeded
random weights.

Tolerances: everything is float32 on the CPU, and program and reference
order their sums differently (a chunked scan against token by token, blocked
attention against the full score matrix), so agreement is to a few float32
roundings accumulated over eight layers: relative errors of 1e-6 to 3e-6 were
read; the limit 1e-4 leaves room and is still 40 times under what one bf16
rounding of any operand gives (4e-3). A piece of the mathematics left out
moves the logits by 2e-3 at the least (read: 3e-3 to 0.5)."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import hybrid
from ray_tpu.models.serving import ContinuousBatchingEngine
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "jamba_reference", os.path.join(ROOT, "perfbench", "references", "jamba.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

TOL = 1e-4
CFG = hybrid.HybridConfig.tiny_runs()
# the same toy in the configuration file's key names, for the reference
C = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 8,
     "num_attention_heads": 4, "num_key_value_heads": 1, "attn_layer_offset": 2,
     "attn_layer_period": 4, "mamba_d_conv": 4, "mamba_d_state": 16,
     "mamba_dt_rank": 8, "mamba_expand": 2, "rms_norm_eps": 1e-6,
     "vocab_size": 512}


@pytest.fixture(scope="module")
def params():
    p = hybrid.init_params(jax.random.PRNGKey(0), CFG)
    # norm weights off 1, so that a norm's weight left out shows as well
    def jitter(path, a):
        if path[-1].key.endswith("norm"):
            key = jax.random.fold_in(jax.random.PRNGKey(9), hash(str(path)) % 1000)
            return a * (1.0 + 0.2 * jax.random.normal(key, a.shape))
        return a
    return jax.tree_util.tree_map_with_path(jitter, p)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, 75), 1, CFG.vocab_size)


@pytest.fixture(scope="module")
def want(params, tokens):
    return ref.logits(params, tokens, C)


def rel(got, want):
    return float(ref.rel_err(jnp.asarray(got), jnp.asarray(want)))


def test_layer_pattern_matches_the_reference():
    assert [tuple(r) for r in CFG.runs()] == ref.runs(C) == [
        ("mamba", 2), ("attn", 1), ("mamba", 3), ("attn", 1), ("mamba", 1)]
    assert CFG.scanned and not hybrid.HybridConfig.tiny_hybrid().scanned
    with pytest.raises(ValueError, match="scanned runs"):
        hybrid.HybridConfig(mamba_layers=(1,)).runs()   # KDA layers beside it


def test_whole_sequence_logits(params, tokens, want):
    # 75 positions: the chunked scan (chunks of 16) ends inside a chunk
    assert rel(hybrid.forward(params, tokens, CFG), want) < TOL


def _without(params, leaf):
    """The weights with one seeded vector of every Mamba layer zeroed."""
    def drop(path, a):
        return jnp.zeros_like(a) if path[-1].key == leaf else a
    return jax.tree_util.tree_map_with_path(drop, params)


@pytest.mark.parametrize("leaf", ["conv_bias", "dt_bias", "D"])
def test_a_seeded_vector_left_out_fails(params, tokens, want, leaf):
    assert rel(hybrid.forward(_without(params, leaf), tokens, CFG), want) > 20 * TOL


@pytest.mark.parametrize("which", [0, 1, 2])
def test_an_inner_norm_left_out_fails(params, tokens, want, which, monkeypatch):
    """The program with the RMSNorm on dt (0), B (1) or C (2) skipped: the
    three calls of `_mamba_inputs`, in that order, once per traced body (the
    three Mamba runs share one traced body)."""
    real, calls = hybrid.rms_norm, [0]

    def skipping(x, w, eps, *rest):
        if x.shape[-1] in (CFG.dt_rank, CFG.d_state):
            calls[0] += 1
            if (calls[0] - 1) % 3 == which:
                return x
        return real(x, w, eps, *rest)

    monkeypatch.setattr(hybrid, "rms_norm", skipping)
    got = hybrid.forward.__wrapped__(params, tokens, CFG)   # traced anew
    assert calls[0] in (3, 9)
    assert rel(got, want) > 20 * TOL


def test_a_scanned_run_is_the_same_layers_unrolled(params, tokens):
    """The second run (three Mamba layers) as `lax.scan` runs it against a
    Python loop over its layers, each given its own slice of the weights."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, CFG.d_model))
    true_len = jnp.asarray([40, 23])
    valid = jnp.arange(40)[None, :] < true_len[:, None]
    rp = params["runs"][2]

    def layer(x, lp):
        _, h = hybrid._normed(CFG, x, lp["mixer_norm"])
        out, state, tail = hybrid._mamba_seq(CFG, lp["mamba"], h, valid, true_len)
        return hybrid._dense_ffn(CFG, lp, x + out), (state, tail)

    x_scan, (s_scan, t_scan) = jax.lax.scan(layer, x, rp)
    x_loop, states, tails = x, [], []
    for i in range(3):
        x_loop, (s, t) = layer(x_loop, jax.tree_util.tree_map(lambda a: a[i], rp))
        states.append(s)
        tails.append(t)
    np.testing.assert_allclose(x_scan, x_loop, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s_scan, jnp.stack(states), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(t_scan, jnp.stack(tails), rtol=1e-5, atol=1e-6)


def test_prefill_then_decode_through_the_slot_state(params, tokens, want):
    """Prompts of 37 and 20 in a bucket of 64, written into slots 2 and 0 of
    a cache of four, then 23 tokens teacher-forced one at a time from that
    state: logits of the prompt pass and of every decoded position against
    the reference's full forward (logits, not tokens). Slot 0 stays live
    beside it; slots 1 and 3 are idle (length 0) and stay so."""
    cache = CFG.make_cache(4, 128)
    assert type(cache).__name__ == "RunsCache"
    assert [a.shape for a in cache.state["ssm"]] == [
        (2, 4, 16, 128), (3, 4, 16, 128), (1, 4, 16, 128)]
    assert cache.state["ssm"][0].dtype == jnp.float32
    assert cache.state["conv"][1].shape == (3, 4, 3, 128)
    assert cache.state["k"].shape == (2, 4, 1, 128, 16)
    row = np.zeros((2, 64), np.int32)
    row[0, :37], row[1, :20] = tokens[0, :37], tokens[1, :20]
    lens = jnp.asarray([37, 20], jnp.int32)
    logits, rows = hybrid.prefill(params, jnp.asarray(row), lens, CFG)
    assert rel(logits[0], want[0, 36]) < TOL and rel(logits[1], want[1, 19]) < TOL
    # the padding left state and tail alone: the same rows from a tight bucket
    _, tight = hybrid.prefill(params, tokens[1:2, :20], jnp.asarray([20]), CFG)
    for a, b in zip(rows["ssm"] + rows["conv"], tight["ssm"] + tight["conv"]):
        np.testing.assert_allclose(a[:, 1:2], b, rtol=1e-4, atol=1e-5)
    first, rows = cache.prefill(params, jnp.asarray(row), lens)
    lengths, toks = cache.write(jnp.zeros((4,), jnp.int32), jnp.zeros((4,), jnp.int32),
                                jnp.asarray([2, 0], jnp.int32), rows, lens, first)
    assert lengths.tolist() == [20, 0, 37, 0]
    assert int(first[0]) == int(jnp.argmax(want[0, 36]))
    for t in range(37, 60):
        step = jnp.zeros((4,), jnp.int32).at[2].set(tokens[0, t]).at[0].set(
            tokens[1, t - 17])
        cache.state, lg, _ = hybrid.decode_logits(params, cache.state, lengths, step,
                                                  None, CFG, 64)
        lengths = lengths + (lengths > 0)
        assert rel(lg[2], want[0, t]) < TOL and rel(lg[0], want[1, t - 17]) < TOL
    assert lengths.tolist() == [43, 0, 60, 0]


_ref_logits = jax.jit(lambda p, t: ref.logits(p, t, C))


def _greedy_reference(params, prompt, n):
    """One fixed shape (the reference is causal: what follows a position
    does not reach it), so one compile."""
    toks = list(prompt)
    for _ in range(n):
        row = np.zeros((1, 32), np.int32)
        row[0, :len(toks)] = toks
        toks.append(int(jnp.argmax(_ref_logits(params, jnp.asarray(row))[0, len(toks) - 1])))
    return toks[len(prompt):]


def test_through_the_engine_a_reused_slot_starts_from_the_admitted_state(params):
    """Two slots, five requests of mixed lengths (three prompt buckets): the
    third to fifth are admitted into slots that hold the state and K/V rows
    of finished ones, and every answer is what the reference gives alone."""
    tracing.clear()
    eng = ContinuousBatchingEngine(params, CFG, num_slots=2, max_len=64)
    prompts = [[5, 9, 17, 300, 2, 2, 40, 41, 42, 43, 44], [7, 7, 3],
               list(range(100, 120)), [1], [11, 12, 13, 14, 15, 16, 17, 18, 19]]
    ids = [eng.submit(p, max_new_tokens=n) for p, n in zip(prompts, (6, 9, 4, 7, 5))]
    eng.run_until_done()
    for p, i, n in zip(prompts, ids, (6, 9, 4, 7, 5)):
        assert eng.result(i)[len(p):] == _greedy_reference(params, p, n), p
    assert type(eng.cache).__name__ == "RunsCache"
    steps = [e["args"] for e in tracing.get_events() if e["name"] == "engine.step"]
    busy = [a for a in steps if a.get("active")]
    assert busy and all(a["state_slots"] == a["active"] and a["kv_rows"] > 0
                        for a in busy)
    prefills = [e["args"] for e in tracing.get_events() if e["name"] == "engine.prefill"]
    assert len(prefills) == 5 and all(
        a["state_layers"] == 6 and a["kv_layers"] == 2 for a in prefills)


def test_the_phases_are_named_in_all_three_call_modes(params, tokens):
    """`mamba`, `scan`, `attention`, `mlp`, `head` name the operations of the
    prompt pass; the step adds `state_write`."""
    lens = jnp.asarray([75, 75], jnp.int32)
    cache = CFG.make_cache(2, 128)
    texts = {
        "forward": hybrid.forward.lower(params, tokens, CFG).as_text(debug_info=True),
        "prefill": hybrid.prefill.lower(params, tokens, lens, CFG).as_text(debug_info=True),
        "decode_step": hybrid.decode_step.lower(
            params, cache.state, lens, lens, None, CFG, 64).as_text(debug_info=True)}
    for mode, scopes in (("forward", ("mamba", "scan", "attention", "mlp")),
                         ("prefill", ("mamba", "scan", "attention", "mlp", "head")),
                         ("decode_step", ("mamba", "scan", "attention", "mlp",
                                          "state_write", "head"))):
        for scope in scopes:
            assert f"/{scope}/" in texts[mode] or f"{scope}/" in texts[mode], (mode, scope)


def test_llm_replica_builds_the_runs_form():
    from ray_tpu.serve.llm import LLMReplica

    r = LLMReplica("tiny_runs", num_slots=2, max_len=32)
    assert len(r({"prompt": [1, 2, 3], "max_new_tokens": 3})) == 6
