"""The benchmark's serve replica for the EvaByte model (EVA attention as a
scanned run): the record, clocks, trace annotations and wrapping of the
engine's entry points are `lib.hybrid_replica.HybridBenchReplica`'s; what
differs is how the model is built (`lib.eva_model`), what `check` compares (prompt
passes and forty decode positions that cross chunk and window closes), and
that the trace's reduction keeps the `eva_decode_attention` kernel's calls."""

from __future__ import annotations

import threading
import time

from perfbench.lib.hybrid_replica import HybridBenchReplica

ATTEND_KERNEL = "eva_decode_attention"


class EvaBenchReplica(HybridBenchReplica):
    def __init__(self, spec: dict):
        t_enter = time.time()
        import jax
        import jax.numpy as jnp

        from perfbench.lib import eva_model, worker
        from ray_tpu.models.serving import ContinuousBatchingEngine

        self.spec = spec
        self.counter = worker.CompileCounter()
        self.spans = worker.Spans()
        self.fatal = None
        try:
            self.device = worker.device_report(1, spec["rehearsal"])
        except RuntimeError as e:
            self.fatal = str(e)   # said through `info` (see BenchReplica)
            return
        t_device = time.time()
        run = spec["config"]["run"]
        self.cfg = cfg = eva_model.model_config(spec["config"])
        self.params = eva_model.make_params(cfg, spec["seed"])
        served = self._served(self.params)
        if spec.get("control"):
            # the control keeps the rounded weights (donated) and `check`
            # makes the seed's again once the engine is gone
            self.params = None
        self.engine = eng = ContinuousBatchingEngine(
            served, cfg, num_slots=run["num_slots"], max_len=run["max_len"])
        self._lock = threading.Lock()
        self.requests, self.entries, self.steps = {}, {}, []
        self._wrap(eng)

        # warm exactly the programs the traffic reaches, through the engine's
        # own cache (donated buffers), as `_dispatch_prefill` and
        # `_dispatch_decode` call it; the buckets are the cache's own: whole
        # windows, every count from the shortest prompt's to the longest's
        warm, cache, n = spec["traffic"]["warm"], eng.cache, eng.num_slots
        for bucket in warm["prefill_buckets"]:
            for nb in warm["admission_batches"]:
                if nb > (cache.max_prefill_batch(bucket) or nb):
                    continue
                lens = jnp.asarray([1] * nb, jnp.int32)
                dropped = jnp.asarray([n] * nb, jnp.int32)  # out of range
                first, rows = cache.prefill(
                    eng.params, jnp.asarray([[0] * bucket] * nb, jnp.int32), lens)
                eng.lengths, eng.tokens = cache.write(
                    eng.lengths, eng.tokens, dropped, rows, lens, first)
        for attn_len in warm["attention_buckets"]:
            eng.lengths, eng.tokens, _ = cache.decode(
                eng.params, eng.lengths, eng.tokens, attn_len, ())
        eng.lengths = jnp.zeros((n,), jnp.int32)
        eng.tokens = jnp.zeros((n,), jnp.int32)
        jax.block_until_ready(cache.state)
        self.times = {"t_enter": t_enter, "t_device": t_device,
                      "t_warm": time.time()}
        self.compile_setup = self.counter.snapshot()

    def stats(self, payload=None):
        """`HybridBenchReplica.stats`; after a traced run the reduction also
        holds, under `kernel_calls`, the `eva_decode_attention` kernel's
        [events, seconds]: one event a layer and decode step (the rows it
        read are the program's own `window_rows` + `summary_rows`). Read
        before the parent's reduction, which removes the trace."""
        from perfbench.lib import xplane
        from perfbench.lib.jamba_replica import _kernel_events

        calls = {}
        if (payload or {}).get("trace"):
            events = _kernel_events(
                xplane.load(xplane.find_xplane(self._trace_dir)), ATTEND_KERNEL)
            calls = {ATTEND_KERNEL: [len(events), sum(t for _, t in events)]}
        out = super().stats(payload)
        if out.get("trace"):
            out["trace"]["kernel_calls"] = calls
        return out

    # --------------------------------------------------------- correctness
    def check(self, payload):
        import gc

        from perfbench.lib import eva_model
        from perfbench.lib.manifest import load_py

        ref = load_py(self.spec["reference_file"])
        tr = self.spec["traffic"]
        got = program_rows(self.engine, payload["samples"], tr["check_decode_steps"])
        # the slot tables (8.6 GB of the chip's 16) make room for the
        # reference; nothing is served any more
        self.engine.stop_driver()
        params, self.engine = self.params, None
        gc.collect()     # the instance's wrapped methods point back at it
        if params is None:   # a control run: remake the seed's weights
            params = eva_model.make_params(self.cfg, self.spec["seed"])
        W = self.cfg.eva_window
        longest = tr["prompt_tokens"]["max"] + tr["answer_tokens"]["max"]
        return compare_with_reference(ref, self.spec["config"], params,
                                      payload["samples"], got, -(-longest // W) * W)


def decode_from(sample: dict, i: int, steps: int, window: int):
    """Where sample i's compared decode positions start, and the tokens fed:
    an even sample starts `steps // 2` positions before the LAST window
    boundary of its prompt and is fed the prompt's own bytes across it
    (a window closes in the middle of the compared positions, under the most
    summaries the prompt can show); an odd one starts at the prompt's end and
    is fed the answer it was given. Either way `steps` consecutive positions
    close at least `steps // chunk` chunks."""
    prompt, answer = list(sample["prompt"]), list(sample["answer"])
    if i % 2 == 0 and len(prompt) > window:
        start = len(prompt) // window * window - steps // 2
        # a prompt of whole windows ends on that boundary: the answer follows
        return start, (prompt + answer)[start:start + steps]
    return len(prompt), answer[:steps]


def program_rows(engine, samples, decode_steps):
    """What the ENGINE that served the window computes for each sample, as
    numpy, through its own slot state after the window (nothing is live any
    more; the stepper is held off).

    Prompt passes: every sample's whole prompt, in the bucket admission ran
    it in and one request to a call, as `_dispatch_prefill` admits
    (`hybrid.prefill`: admission's program with the logits returned); an
    even sample's prompt also up to where its decode starts (`decode_from`).
    The state rows of the pass a sample decodes behind go into a slot of the
    engine's cache, spread over it, by the engine's own `cache.write`.

    Decode: the samples, all live at once among the engine's idle slots,
    are decoded `decode_steps` positions, teacher-forced, by
    `hybrid.decode_logits`: the step program's body over the engine's donated
    state, as `_dispatch_decode` runs it.

    -> per sample {"prefill": {position: logits of the first head},
    "decode": {position: logits}}."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import hybrid
    from ray_tpu.models.serving import _attn_bucket

    cfg, served, cache = engine.cfg, engine.params, engine.cache
    B, max_len, W = engine.num_slots, engine.max_len, cfg.eva_window
    spread = [(7 * j + 3) % B for j in range(B)] if B % 7 else list(range(B))
    out = [{"prefill": {}, "decode": {}} for _ in samples]
    with engine._step_lock:
        slot = {i: spread[i] for i in range(len(samples))}
        starts = [decode_from(s, i, decode_steps, W) for i, s in enumerate(samples)]
        for i, s in enumerate(samples):
            for upto in sorted({len(s["prompt"]), starts[i][0]}):
                toks = np.zeros((1, cache.prompt_bucket(upto)), np.int32)
                toks[0, :upto] = s["prompt"][:upto]
                lens = jnp.asarray([upto], jnp.int32)
                logits, rows = hybrid.prefill(served, jnp.asarray(toks), lens, cfg)
                out[i]["prefill"][upto - 1] = np.asarray(logits[0])
                to = slot[i] if upto == starts[i][0] else B       # B: dropped
                engine.lengths, engine.tokens = cache.write(
                    engine.lengths, engine.tokens, jnp.asarray([to], jnp.int32),
                    rows, lens, jnp.zeros((1,), jnp.int32))
        attn_len = _attn_bucket(max(n for n, _ in starts) + decode_steps, max_len)
        for t in range(decode_steps):
            toks = np.zeros((B,), np.int32)
            for i, (_, fed) in enumerate(starts):
                toks[slot[i]] = (fed[t:t + 1] or [0])[0]
            cache.state, logits, _ = hybrid.decode_logits(
                served, cache.state, engine.lengths, jnp.asarray(toks), None,
                cfg, attn_len)
            engine.lengths = engine.lengths + (engine.lengths > 0)
            logits = np.asarray(logits)
            for i, (n, fed) in enumerate(starts):
                if t < len(fed):
                    out[i]["decode"][n + t] = logits[slot[i]]
        engine.lengths = jnp.zeros((B,), jnp.int32)
        engine.tokens = jnp.zeros((B,), jnp.int32)
        cache.state = None      # the tables go: `check` needs their room
    return out


def compare_with_reference(ref, c, params, samples, got, ref_len) -> dict:
    """The plain float32 reference against what was served, three numbers.

    `token_gap_mean_spacings`: the reference teacher-forced over prompt +
    answer (ONE fixed shape, `ref_len` positions, whole windows): for every
    byte the engine chose, how far the reference's first-head logit of it
    lies under the reference's top logit, in bf16 spacings of that logit
    (with random weights the top two are often a rounding apart, so bytes
    are not compared; a lower precision pushes the mean gap up).

    `prefill_logits_rel_err`, `decode_logits_rel_err`: the largest relative
    error, over the samples, of the program's first-head logits
    (`program_rows`: the prompt passes as admission runs them; the decode
    positions through the engine's slot state) against the reference's at
    the same positions. `closes` counts what the decode positions crossed."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    W, C = c["window_size"], c["chunk_size"]

    @jax.jit
    def reference_rows(p, toks):
        want = ref.logits(p, toks, c)[0, :, 0]                # [ref_len, V]
        nxt = jnp.roll(toks[0], -1)        # the byte that followed each position
        top = jnp.max(want, axis=-1)
        chosen = jnp.take_along_axis(want, nxt[:, None], axis=-1)[:, 0]
        spacing = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(jnp.abs(top), 1e-30))) - 7)
        return want, (top - chosen) / spacing

    gaps, pre, dec, per_answer = [], [], [], []
    chunks = windows = 0
    for i, (s, g) in enumerate(zip(samples, got)):
        prompt, answer = list(s["prompt"]), list(s["answer"])
        toks = np.zeros((1, ref_len), np.int32)
        toks[0, :len(prompt) + len(answer)] = prompt + answer
        want, gap = reference_rows(params, jnp.asarray(toks))
        gap = np.asarray(gap)[len(prompt) - 1: len(prompt) - 1 + len(answer)]
        gaps.extend(gap.tolist())
        errs = {kind: {pos: float(ref.rel_err(jnp.asarray(row), want[pos]))
                       for pos, row in g[kind].items()} for kind in g}
        decoded = sorted(errs["decode"])
        n_chunks = sum((pos + 1) % C == 0 for pos in decoded)
        n_windows = sum((pos + 1) % W == 0 for pos in decoded)
        chunks, windows = chunks + n_chunks, windows + n_windows
        pre.append(max(errs["prefill"].values()))
        dec.append(max(errs["decode"].values(), default=0.0))
        per_answer.append({"prompt_len": len(prompt), "answer_len": len(answer),
                           "mean_gap_spacings": float(gap.mean()),
                           "off_argmax": int((gap > 0).sum()),
                           "prefill_logits_rel_err": pre[-1],
                           "decode_logits_rel_err": dec[-1],
                           "decoded": [decoded[0], decoded[-1]] if decoded else [],
                           "chunks_closed": n_chunks, "windows_closed": n_windows})
    return {"token_gap_mean_spacings": float(np.mean(gaps)),
            "prefill_logits_rel_err": max(pre), "decode_logits_rel_err": max(dec),
            "closes": {"chunks": chunks, "windows": windows,
                       "fewest_chunks_a_sample": min(a["chunks_closed"]
                                                     for a in per_answer)},
            "answers": per_answer, "tokens_compared": len(gaps)}
