"""Continuous-batching engine: parity with the one-shot generate loop."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import ModelConfig, init_params
from ray_tpu.models.inference import generate
from ray_tpu.models.serving import ContinuousBatchingEngine, _write_rows

CFG = ModelConfig.tiny()
PARAMS = init_params(jax.random.PRNGKey(0), CFG)
MAX_LEN = 64


def _reference(prompt, n):
    out = generate(PARAMS, jnp.asarray([prompt], jnp.int32), CFG,
                   max_new_tokens=n, max_len=MAX_LEN, temperature=0.0)
    return np.asarray(out)[0].tolist()


def test_single_request_matches_generate():
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=2, max_len=MAX_LEN)
    prompt = [5, 17, 400, 3]
    assert eng.generate(prompt, max_new_tokens=8) == _reference(prompt, 8)


def test_interleaved_requests_match_individual_runs():
    """Requests joining mid-flight must not perturb each other."""
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=4, max_len=MAX_LEN)
    p1, p2, p3 = [1, 2, 3], [100, 200, 300, 400, 17], [7]
    r1 = eng.submit(p1, max_new_tokens=10)
    eng.step()
    eng.step()
    r2 = eng.submit(p2, max_new_tokens=6)   # joins while r1 decodes
    eng.step()
    r3 = eng.submit(p3, max_new_tokens=4)
    eng.run_until_done()
    assert eng.result(r1) == _reference(p1, 10)
    assert eng.result(r2) == _reference(p2, 6)
    assert eng.result(r3) == _reference(p3, 4)


def test_more_requests_than_slots():
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=2, max_len=MAX_LEN)
    prompts = [[i + 1, i + 2] for i in range(5)]
    rids = [eng.submit(p, max_new_tokens=5) for p in prompts]
    eng.run_until_done()
    for rid, p in zip(rids, prompts):
        assert eng.result(rid) == _reference(p, 5)


def test_eos_stops_generation():
    # pick the first greedily generated token as "EOS" so it fires at once
    prompt = [9, 8, 7]
    ref = _reference(prompt, 4)
    eos = ref[len(prompt)]
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=2, max_len=MAX_LEN,
                                   eos_token=eos)
    out = eng.generate(prompt, max_new_tokens=16)
    assert out == prompt  # EOS stripped, nothing else generated


def test_bucketed_prefill_and_validation():
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=2, max_len=MAX_LEN)
    # length 11 -> 16-bucket: padding must not perturb outputs
    prompt = list(range(20, 31))
    assert eng.generate(prompt, max_new_tokens=6) == _reference(prompt, 6)

    with pytest.raises(ValueError, match="prompt length"):
        eng.submit(list(range(MAX_LEN)))
    with pytest.raises(ValueError, match="empty"):
        eng.submit([])


def test_generate_stream_matches_generate():
    """Streaming yields exactly the generated suffix, token by token."""
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=2, max_len=MAX_LEN)
    prompt = [5, 17, 400, 3]
    full = eng.generate(prompt, max_new_tokens=8)
    eng2 = ContinuousBatchingEngine(PARAMS, CFG, num_slots=2, max_len=MAX_LEN)
    streamed = list(eng2.generate_stream(prompt, max_new_tokens=8))
    assert prompt + streamed == full


def test_int8_quantized_engine_quality_and_memory():
    """w8a16 serving (VERDICT r04 #8): quantize_model_params halves weight
    bytes; prefill logits stay close to the bf16 model; the engine runs
    end to end with quantize_weights=True."""
    from ray_tpu.models.inference import prefill
    from ray_tpu.models.serving import quantize_model_params

    qparams = quantize_model_params(PARAMS, CFG)

    def leaf_bytes(tree):
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(tree))

    big = {k: v for k, v in PARAMS["layers"].items() if v.ndim == 3}
    big_q = {k: qparams["layers"][k] for k in big}
    # fp32 tiny-model weights -> int8 + fp32 row scales: ~4x smaller
    assert leaf_bytes(big_q) < 0.3 * leaf_bytes(big)

    tokens = jnp.asarray([[5, 17, 400, 3, 9, 22, 7, 1]], jnp.int32)
    ref_logits, _ = prefill(PARAMS, tokens, CFG, MAX_LEN)
    q_logits, _ = prefill(qparams, tokens, CFG, MAX_LEN)
    ref = np.asarray(ref_logits, np.float32)
    qn = np.asarray(q_logits, np.float32)
    scale = np.abs(ref).max() + 1e-6
    assert np.abs(ref - qn).max() / scale < 0.08, \
        np.abs(ref - qn).max() / scale

    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=2, max_len=MAX_LEN,
                                   quantize_weights=True)
    out = eng.generate([5, 17, 400, 3], max_new_tokens=8)
    assert len(out) == 4 + 8  # prompt + generated
    assert all(0 <= t < CFG.vocab_size for t in out)


def test_decode_step_donation_clean():
    """The fused decode step donates the K/V/length buffers: steady-state
    stepping hands back the buffers it was given (pointers stay within the
    initial donated set, the number of live cache-shaped arrays is stable),
    and tokens and lengths stay on device between steps.

    What this cannot see: what the program does BETWEEN the aliased input
    and output. On the CPU the pointers alias whether or not the compiled
    step relayouts the whole cache around its row write, as XLA:TPU did up
    to PR 26 (four copies of 1.6 GB a step, all inside aliased buffers).
    That is asked of the chip's compiler, in `tests/test_chip_compile.py`
    (`test_decode_step_keeps_the_cache_layout`)."""
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=2, max_len=MAX_LEN)
    eng.submit([5, 17, 400, 3], max_new_tokens=60)
    eng.step()  # prefill dispatch
    eng.step()  # first fused decode: compile + donation warm-up
    cache_shape = eng.k.shape
    # XLA may alias a donated output onto ANY dead donated input of matching
    # shape/dtype, so k/v pointers can swap — the SET must be closed.
    ptrs = {eng.k.unsafe_buffer_pointer(), eng.v.unsafe_buffer_pointer()}
    n_live = sum(1 for a in jax.live_arrays() if a.shape == cache_shape)
    for _ in range(10):
        eng.step()
        assert eng.k.unsafe_buffer_pointer() in ptrs
        assert eng.v.unsafe_buffer_pointer() in ptrs
        assert isinstance(eng.tokens, jax.Array)
        assert isinstance(eng.lengths, jax.Array)
        now_live = sum(1 for a in jax.live_arrays() if a.shape == cache_shape)
        assert now_live <= n_live  # no per-step full-cache reallocation


def _project_qkv_as_the_parent_of_pr35(cfg, p, x, cos, sin):
    """What `decode_step_fused`'s body composed up to PR 34, in the layout
    `serving._one_row_qkv` returns: the shared `_project_qkv` on [B, 1, d],
    the one position dropped, the query heads grouped by kv head."""
    from ray_tpu.models.transformer import _project_qkv

    q, k, v = _project_qkv(cfg, p, x, cos, sin)  # [B, 1, heads, hd]
    q = q[:, 0].reshape(x.shape[0], cfg.n_kv_heads, -1, cfg.head_dim)
    return q, k[:, 0].astype(cfg.dtype), v[:, 0].astype(cfg.dtype)


def _decode_step_with(monkeypatch, qkv):
    """`decode_step_fused`'s own text, traced anew around `qkv` (jax keeps
    traces by the function's identity: a function of its own)."""
    from ray_tpu.models import serving

    def step(*args):
        monkeypatch.setattr(serving, "_one_row_qkv", qkv)
        return serving.decode_step_fused.__wrapped__(*args)

    return jax.jit(step, static_argnums=(5, 6))


@pytest.mark.parametrize("weights", ["bf16", "int8"])
def test_decode_step_equals_the_one_composing_project_qkv(monkeypatch, weights):
    """The decode step spells its projections itself (`_one_row_qkv`: flat
    bf16 products behind a barrier, so that on the chip each reads its
    weight in place). Same mathematics as the shared `_project_qkv` the
    parent composed: next tokens, lengths and BOTH caches (the written K/V
    rows among them) equal bit for bit on the CPU path, over four steps at
    ragged lengths with idle slots, with bf16 weights and with the
    int8-dequantised weights the benchmark's control runs."""
    import dataclasses

    from ray_tpu.models import serving

    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16)
    params = init_params(jax.random.PRNGKey(0), cfg)
    if weights == "int8":
        params = serving.quantize_model_params(params, cfg)
    shape = (cfg.n_layers, 6, cfg.n_kv_heads, MAX_LEN, cfg.head_dim)

    def run(qkv):
        step = _decode_step_with(monkeypatch, qkv)
        k = jax.random.normal(jax.random.PRNGKey(1), shape).astype(cfg.dtype)
        v = jax.random.normal(jax.random.PRNGKey(2), shape).astype(cfg.dtype)
        lengths = jnp.asarray([5, 0, 33, 17, 0, 1], jnp.int32)
        tokens = jnp.asarray([3, 9, 100, 7, 0, 42], jnp.int32)
        seen = []
        for _ in range(4):
            k, v, lengths, tokens = step(params, k, v, lengths, tokens, cfg, MAX_LEN)
            seen.append(np.asarray(tokens))
        return [np.asarray(a.astype(jnp.float32)) for a in (k, v)] + [
            np.asarray(lengths), np.stack(seen)]

    ours = run(serving._one_row_qkv)
    parents = run(_project_qkv_as_the_parent_of_pr35)
    assert ours[2].tolist() == [9, 0, 37, 21, 0, 5]
    assert len({tuple(t) for t in ours[3].T}) > 1  # not one token everywhere
    for a, b in zip(ours, parents):
        np.testing.assert_array_equal(a, b)


def _write_rows_one_row_window(cache, rows, lengths):
    """The row write as it was up to PR 26, kept as the plain reference:
    one `dynamic_update_slice` of a [1, hd] window per (layer, slot, head).
    A position past the end is clamped to the last row."""
    def write_row(c, new, pos):  # c [max_len, hd] <- new [1, hd] at row pos
        return jax.lax.dynamic_update_slice(c, new, (pos, 0))

    wr = jax.vmap(jax.vmap(jax.vmap(write_row, in_axes=(0, 0, None)),  # kvh
                           in_axes=(0, 0, 0)),                         # B
                  in_axes=(0, 0, None))                                # L
    return wr(cache, rows[:, :, :, None], lengths)


def _check_write_rows(monkeypatch, execution, dims, max_len, dtype, fill):
    """`write_rows` through one of its two executions against the one-row
    window: bit-identical for every slot that holds something, every other
    slot untouched. "kernel" steers the device branch (`_util.on_tpu`) and
    interprets the Pallas call; where the shape rule leaves a shape on the
    loop, that is asserted and the loop is what runs."""
    from ray_tpu.ops import cache as cache_ops
    from ray_tpu.ops.pallas import _util

    L, kvh, hd = dims
    R = 32 // jnp.dtype(dtype).itemsize
    inside = sorted({p for p in (0, 1, R - 1, R, 2 * R - 1, max_len - 2,
                                 max_len - 1) if 0 <= p < max_len})
    past = [max_len, max_len + 1, max_len + R, 10 * max_len]
    positions = {"ragged": inside + past + [0],  # idle slots first and last
                 "all_idle": [0] * 5,
                 "all_busy": [p for p in inside if p > 0]}[fill]
    lengths = jnp.asarray(positions, jnp.int32)
    B = len(positions)
    kc, kr = jax.random.split(jax.random.PRNGKey(max_len))
    cache = (jax.random.normal(kc, (L, B, kvh, max_len, hd)) + 3.0).astype(dtype)
    rows = (jax.random.normal(kr, (L, B, kvh, hd)) - 3.0).astype(dtype)

    if execution == "kernel":
        monkeypatch.setattr(_util, "on_tpu", lambda: True)
        monkeypatch.setattr(_util, "interpret_mode", lambda: True)
        tiles = max_len % R == 0 and hd % 128 == 0
        assert cache_ops.uses_write_kernel(cache) == tiles
    else:
        assert not cache_ops.uses_write_kernel(cache)  # the CPU here
    got = np.asarray(jax.jit(_write_rows)(cache, rows, lengths), np.float32)
    ref = np.asarray(_write_rows_one_row_window(cache, rows, lengths), np.float32)
    written = [b for b, pos in enumerate(positions) if 0 < pos < max_len]
    np.testing.assert_array_equal(got[:, written], ref[:, written])
    want, new = np.array(cache, np.float32), np.asarray(rows, np.float32)
    for b in written:
        want[:, b, :, positions[b]] = new[:, b]
    # a slot of length 0 and a slot at or past the end: untouched
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("execution", ["loop", "kernel"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("max_len", [64, 40, 12])
def test_write_rows_matches_the_one_row_window(max_len, dtype, execution,
                                               monkeypatch):
    """The step's block write (read the tile-aligned R rows around the
    position, select the new row in, write the block back) against the
    one-row window it replaced, through the loop AND through the kernel:
    bit-identical caches for every slot whose position is inside the cache,
    at the block edges and the cache's end, for `max_len` a multiple of R,
    not a multiple (40 in bf16) and under R (12; both loop only, by the
    shape rule); a slot past the end (the hybrid model's idle slots keep
    counting) writes nothing, and a slot of length 0 holds nothing and
    writes nothing."""
    _check_write_rows(monkeypatch, execution, (3, 2, 128), max_len, dtype,
                      "ragged")


@pytest.mark.parametrize("execution", ["loop", "kernel"])
@pytest.mark.parametrize("fill", ["ragged", "all_idle", "all_busy"])
@pytest.mark.parametrize("dims", [(3, 2, 128), (2, 1, 128), (2, 1, 72)],
                         ids=["dense", "one_kv_head", "latent_lanes"])
def test_write_rows_at_every_callers_shape_and_fill(dims, fill, execution,
                                                    monkeypatch):
    """The three callers' shapes in small (the dense step's; the runs
    form's ONE kv head of 128; one "kv head" whose last dimension is no
    multiple of 128 lanes and so stays on the loop, as the hybrid step's
    latent rows did before they were stored in whole tiles), with idle slots among the busy ones, every slot idle (the kernel
    then holds one block and passes it through) and every slot busy."""
    _check_write_rows(monkeypatch, execution, dims, 64, jnp.bfloat16, fill)


def test_the_write_kernel_is_chosen_by_what_the_code_can_see(monkeypatch):
    """Platform and shape, as `decode_attention.uses_decode_kernel` chooses:
    no option, no model's name. The three cells' caches, then the shapes the
    rule leaves on the loop."""
    from ray_tpu.ops import cache as cache_ops
    from ray_tpu.ops.pallas import _util

    bf16 = lambda *dims: jax.ShapeDtypeStruct(dims, jnp.bfloat16)
    chat, burst, longgen = (bf16(24, 32, 8, 1024, 128), bf16(2, 256, 1, 1024, 128),
                            bf16(2, 64, 1, 8192, 576))
    assert not cache_ops.uses_write_kernel(chat)  # the CPU here
    monkeypatch.setattr(_util, "on_tpu", lambda: True)
    assert cache_ops.uses_write_kernel(chat)
    assert cache_ops.uses_write_kernel(burst)
    assert not cache_ops.uses_write_kernel(longgen)  # 4.5 lane tiles
    assert not cache_ops.uses_write_kernel(bf16(24, 32, 8, 1000, 128))  # 62.5 blocks
    assert not cache_ops.uses_write_kernel(bf16(2, 4, 2, 8, 128))  # under R
    assert cache_ops.uses_write_kernel(
        jax.ShapeDtypeStruct((2, 4, 2, 8, 128), jnp.float32))  # R is 8 there
    assert not cache_ops.uses_write_kernel(bf16(96, 4, 16, 1024, 128))  # 4 x 6 MB


def test_progress_and_submit_not_blocked_during_step():
    """Satellite: the engine must hold only `_step_lock` across device
    waits, so streaming `progress()` reads and new `submit()`s complete
    while a step is blocked on the device."""
    import threading
    import time

    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=2, max_len=MAX_LEN)
    rid = eng.submit([1, 2, 3], max_new_tokens=30)
    eng.step()  # prefill
    eng.step()  # warm decode (drains pending-first so _reap is the sync)

    entered = threading.Event()
    release = threading.Event()

    def slow_to_host(arr):
        entered.set()
        release.wait(5.0)
        return np.asarray(arr)

    eng._to_host = slow_to_host  # instance attr shadows the staticmethod
    stepper = threading.Thread(target=eng.step)
    stepper.start()
    try:
        assert entered.wait(5.0), "step never reached the host sync"
        t0 = time.perf_counter()
        toks, done = eng.progress(rid)
        rid2 = eng.submit([4, 5], max_new_tokens=4)
        elapsed = time.perf_counter() - t0
        assert elapsed < 0.5, f"bookkeeping blocked {elapsed:.2f}s behind a step"
        assert not done
    finally:
        release.set()
        stepper.join(10.0)
        del eng._to_host  # restore the real sync
    eng.run_until_done()
    assert eng.result(rid) == _reference([1, 2, 3], 30)
    assert eng.result(rid2) == _reference([4, 5], 4)


def test_quantize_int8_roundtrip_parity():
    """w8a16 numerics: per-channel absmax int8 round-trip error is bounded
    by half a quantization step per row."""
    from ray_tpu.ops.pallas.quant import dequantize_int8, quantize_int8

    w = jax.random.normal(jax.random.PRNGKey(1), (64, 128), jnp.float32)
    vals, scales = quantize_int8(w)
    assert vals.dtype == jnp.int8
    assert scales.shape == (64, 1)  # per-channel (per-row) scales
    back = dequantize_int8(vals, scales, jnp.float32)
    err = np.abs(np.asarray(back) - np.asarray(w))
    bound = np.asarray(scales) * 0.5 + 1e-6
    assert (err <= bound).all(), float((err - bound).max())


def test_quantized_engine_matches_quantized_reference():
    """quantize_weights=True must be EXACTLY the quantized model run through
    the reference generate loop — the fast decode path adds no numerics of
    its own on top of the quantization."""
    from ray_tpu.models.serving import quantize_model_params

    qparams = quantize_model_params(PARAMS, CFG)
    prompt = [5, 17, 400, 3]
    ref = generate(qparams, jnp.asarray([prompt], jnp.int32), CFG,
                   max_new_tokens=8, max_len=MAX_LEN, temperature=0.0)
    ref = np.asarray(ref)[0].tolist()
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=2, max_len=MAX_LEN,
                                   quantize_weights=True)
    assert eng.generate(prompt, max_new_tokens=8) == ref


def test_batched_bucketed_admission_parity():
    """All same-bucket waiting requests are admitted in ONE prefill call per
    bucket; a single step() drains the whole waiting queue into free slots
    without perturbing outputs."""
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=4, max_len=MAX_LEN)
    prompts = [[1, 2, 3], [4, 5], list(range(40, 51)), [9]]  # mixed buckets
    rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
    eng.step()
    with eng._lock:
        assert len(eng._active) == 4  # one step admitted everything
        assert not eng._waiting
    eng.run_until_done()
    for rid, p in zip(rids, prompts):
        assert eng.result(rid) == _reference(p, 6)


def test_driver_mode_concurrent_generates():
    """Driver-thread mode: concurrent blocking generates and a streaming
    read all complete against the background stepper, with full parity."""
    from concurrent.futures import ThreadPoolExecutor

    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=4, max_len=MAX_LEN)
    eng.start_driver()
    try:
        prompts = [[1, 2, 3], [100, 200, 300, 400, 17], [7], [9, 8]]
        with ThreadPoolExecutor(max_workers=4) as ex:
            futs = [ex.submit(eng.generate, p, max_new_tokens=6, timeout=120)
                    for p in prompts]
            outs = [f.result(timeout=120) for f in futs]
        for p, out in zip(prompts, outs):
            assert out == _reference(p, 6)
        streamed = list(eng.generate_stream([5, 6], max_new_tokens=5))
        assert [5, 6] + streamed == _reference([5, 6], 5)
    finally:
        eng.stop_driver()


def _device_lengths(eng):
    return np.asarray(eng.lengths).tolist()


def test_a_retired_slot_has_length_zero_on_the_next_step():
    """An idle slot has length 0 (the decode step reads none of its rows
    and leaves it at 0); the engine zeroes a slot's device length at the end
    of the step that frees it."""
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=3, max_len=MAX_LEN)
    short = eng.submit([1, 2, 3], max_new_tokens=3)
    eng.submit([4, 5, 6, 7, 8], max_new_tokens=12)
    seen_freed = False
    for _ in range(20):
        left = eng.step()
        lengths = _device_lengths(eng)
        for slot in range(eng.num_slots):
            # busy: the host's shadow position IS the device length; idle
            # (never used, or freed in this very step): 0, and it stays 0
            want = eng._slot_pos[slot] if slot in eng._active else 0
            assert lengths[slot] == want, (slot, lengths, eng._slot_pos)
        if eng.result(short) is not None and left:
            seen_freed = True  # one retired, one still decoding beside it
        if not left:
            break
    assert seen_freed and _device_lengths(eng) == [0, 0, 0]


def test_a_slot_freed_and_readmitted_at_once_gets_the_new_prompts_length():
    """The retire program is dispatched at the end of the step that frees a
    slot, the next prompt's rows in the step after: the zero never lands on
    the new request."""
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=1, max_len=MAX_LEN)
    first = eng.submit([1, 2, 3], max_new_tokens=2)
    prompt = list(range(20, 31))
    second = eng.submit(prompt, max_new_tokens=6)  # waits for the one slot
    while eng.result(first) is None:
        eng.step()
    assert _device_lengths(eng) == [0] and 0 not in eng._active
    eng.step()  # admits `second` into the slot freed a step ago
    assert 0 in eng._active
    assert _device_lengths(eng) == [eng._slot_pos[0]] and eng._slot_pos[0] >= len(prompt)
    eng.run_until_done()
    assert eng.result(second) == _reference(prompt, 6)
    assert _device_lengths(eng) == [0]


def test_mixed_batch_answers_are_those_of_the_tree_before_the_idle_length_rule():
    """Greedy answers of a batch that joins, retires and re-admits while
    others decode and slots sit idle: token for token what the parent of
    PR 29 gave on this (CPU, einsum) path, recorded there."""
    prompts = [[1, 2, 3], [100, 200, 300, 400, 17], [7], list(range(20, 31)),
               [9, 8], [33] * 20, [5, 17, 400, 3]]
    news = [10, 3, 6, 12, 1, 7, 5]
    parent = [[200, 444, 312, 428, 335, 261, 261, 261, 261, 99], [60, 340, 382],
              [122, 408, 122, 408, 205, 205],
              [396, 479, 479, 479, 479, 479, 479, 479, 479, 136, 479, 479], [134],
              [390, 77, 442, 375, 411, 436, 390], [100, 100, 100, 394, 478]]
    eng = ContinuousBatchingEngine(PARAMS, CFG, num_slots=4, max_len=MAX_LEN)
    rids = []
    for i, (p, n) in enumerate(zip(prompts, news)):
        rids.append(eng.submit(p, max_new_tokens=n))
        if i % 2:
            eng.step()
    eng.run_until_done()
    assert [eng.result(r)[len(p):] for r, p in zip(rids, prompts)] == parent
