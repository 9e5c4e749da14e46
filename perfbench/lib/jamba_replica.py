"""The benchmark's serve replica for the Jamba model (Mamba and attention
mixers as scanned runs): the record, clocks, trace annotations, wrapping of
the engine's entry points and warm-up through the engine's cache interface
are `lib.hybrid_replica.HybridBenchReplica`'s; what differs is how the model
is built (`lib.jamba_model`), what `check` compares (no routing here), and
that the trace's reduction keeps the `selective_scan` kernel's calls."""

from __future__ import annotations

import re
import threading
import time

from perfbench.lib.hybrid_replica import HybridBenchReplica

SCAN_KERNEL = "selective_scan"
STEP_KERNEL = "selective_step"


class JambaBenchReplica(HybridBenchReplica):
    def __init__(self, spec: dict):
        t_enter = time.time()
        import jax
        import jax.numpy as jnp

        from perfbench.lib import jamba_model, worker
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
        self.cfg = cfg = jamba_model.model_config(spec["config"])
        self.params = jamba_model.make_params(cfg, spec["seed"])
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
        # `_dispatch_decode` call it
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
        holds, under `kernel_calls`, every device event of the `selective_scan`
        kernel as [batch, positions, seconds] (its roofline metric prices each
        call from its shape) and the `selective_step` kernel's [events,
        seconds]. Read before the parent's reduction, which removes the trace."""
        from perfbench.lib import xplane

        calls = {}
        if (payload or {}).get("trace"):
            planes = xplane.load(xplane.find_xplane(self._trace_dir))
            calls = {SCAN_KERNEL: scan_calls(planes), STEP_KERNEL: step_calls(planes)}
        out = super().stats(payload)
        if out.get("trace"):
            out["trace"]["kernel_calls"] = calls
        return out

    # --------------------------------------------------------- correctness
    def check(self, payload):
        import gc

        from perfbench.lib import jamba_model
        from perfbench.lib.manifest import load_py

        ref = load_py(self.spec["reference_file"])
        tr = self.spec["traffic"]
        got = program_rows(self.engine, payload["samples"],
                           tr["prompt_tokens"]["max"], tr["check_decode_steps"])
        params = self.params
        if params is None:   # a control run: drop the engine, remake the seed's
            self.engine.stop_driver()
            self.engine = None
            gc.collect()     # the instance's wrapped methods point back at it
            params = jamba_model.make_params(self.cfg, self.spec["seed"])
        return compare_with_reference(
            ref, self.spec["config"], params, payload["samples"], got,
            tr["prompt_tokens"]["max"] + tr["answer_tokens"]["max"])


def _kernel_events(planes, kernel: str):
    """(instruction text, seconds) of every device event whose instruction
    is NAMED after the kernel (the custom call, or the fusion that holds it)."""
    from perfbench.lib import xplane

    return [(op, dur / 1e9) for name, plane in planes.items()
            if name.startswith("/device:")
            for op, _, dur in plane.get(xplane.OPS_LINE, [])
            if kernel in op.split("=")[0]]


def scan_calls(planes) -> list:
    """[[batch, positions, seconds], ...], one entry per event of the
    `selective_scan` kernel; the shape is that of y, f32[batch, positions,
    d_inner], the first such shape in the instruction."""
    shape = re.compile(r"f32\[(\d+),(\d+),\d+\]")
    found = ((shape.search(op), t) for op, t in _kernel_events(planes, SCAN_KERNEL))
    return [[int(m.group(1)), int(m.group(2)), t] for m, t in found if m]


def step_calls(planes) -> list:
    """[events, seconds] of the `selective_step` kernel: one event a Mamba
    layer and decode step (its result is the whole stacked state, so the
    shape says nothing of the slots it touched: the metric takes those from
    the program's `state_slots`)."""
    events = _kernel_events(planes, STEP_KERNEL)
    return [len(events), sum(t for _, t in events)]


def program_rows(engine, samples, longest_prompt, decode_steps):
    """What the ENGINE that served the window computes for each sample, as
    numpy, through its own slot state after the window (nothing is live any
    more; the stepper is held off).

    Prefill: every sample's whole prompt and its first half are admitted the
    way `_dispatch_prefill` admits, in two of the (batch, bucket) shapes the
    window used: as many requests to a call as a call may have at a quarter
    of the call's token budget, and at the bucket of the longest prompt
    (`hybrid.prefill`: admission's program with the logits returned). The
    state rows of the whole prompts go into slots of the engine's cache,
    spread over it, by the engine's own `cache.write`.

    Decode: the samples, all live at once among the engine's idle slots, are
    decoded `decode_steps` tokens, teacher-forced, by `hybrid.decode_logits`:
    the step program's body over the engine's donated state, as
    `_dispatch_decode` runs it.

    -> per sample {position: logits}."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import hybrid
    from ray_tpu.models.serving import _attn_bucket, _bucket_len

    cfg, served, cache = engine.cfg, engine.params, engine.cache
    B, max_len = engine.num_slots, engine.max_len
    big = _bucket_len(longest_prompt, max_len)
    shapes = [(cache.max_prefill_batch(b), b)
              for b in sorted({min(big, max(8, cfg.prefill_tokens // 16)), big})]
    spread = [(7 * j + 3) % B for j in range(B)] if B % 7 else list(range(B))
    out = [{} for _ in samples]
    with engine._step_lock:
        slot = {i: spread[i] for i in range(len(samples))}
        calls = {shape: [] for shape in shapes}
        for i, s in enumerate(samples):
            n = len(s["prompt"])
            shape = next(sh for sh in shapes if sh[1] >= n)
            for upto in sorted({n, max(1, n // 2)}, reverse=True):
                calls[shape].append((i, upto))
        for (most, bucket), reqs in calls.items():
            for at in range(0, len(reqs), most):
                group = reqs[at:at + most]
                toks = np.zeros((most, bucket), np.int32)   # one program a shape
                lens, slots = [1] * most, [B] * most        # B: dropped
                for j, (i, upto) in enumerate(group):
                    toks[j, :upto] = samples[i]["prompt"][:upto]
                    lens[j] = upto
                    if upto == len(samples[i]["prompt"]):
                        slots[j] = slot[i]
                lens = jnp.asarray(lens, jnp.int32)
                logits, rows = hybrid.prefill(served, jnp.asarray(toks), lens, cfg)
                for j, (i, upto) in enumerate(group):
                    out[i][upto - 1] = np.asarray(logits[j])
                engine.lengths, engine.tokens = cache.write(
                    engine.lengths, engine.tokens, jnp.asarray(slots, jnp.int32),
                    rows, lens, jnp.zeros((most,), jnp.int32))
        attn_len = _attn_bucket(
            max(len(s["prompt"]) for s in samples) + decode_steps, max_len)
        for t in range(decode_steps):
            toks = np.zeros((B,), np.int32)
            for i, s in enumerate(samples):
                toks[slot[i]] = (s["answer"][t:t + 1] or [0])[0]
            cache.state, logits, _ = hybrid.decode_logits(
                served, cache.state, engine.lengths, jnp.asarray(toks), None,
                cfg, attn_len)
            engine.lengths = engine.lengths + (engine.lengths > 0)
            logits = np.asarray(logits)
            for i, s in enumerate(samples):
                if t < len(s["answer"]):
                    out[i][len(s["prompt"]) + t] = logits[slot[i]]
        engine.lengths = jnp.zeros((B,), jnp.int32)
        engine.tokens = jnp.zeros((B,), jnp.int32)
    return out


def compare_with_reference(ref, c, params, samples, got, ref_len) -> dict:
    """The plain float32 reference against what was served, two numbers.

    `token_gap_mean_spacings`: the reference teacher-forced over prompt +
    answer (ONE fixed shape, `ref_len` positions): for every token the engine
    chose, how far the reference's logit of it lies under the reference's
    top logit, in bf16 spacings of that logit (with random weights the top
    two are often a rounding apart, so tokens are not compared; a lower
    precision pushes the mean gap up).

    `prefill_logits_rel_err`: the largest relative error, over the samples,
    of the program's logits (`program_rows`: prefill in admission's shapes,
    then decode through the engine's slot state) against the reference's at
    the same positions."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def reference_rows(p, toks):
        want = ref.logits(p, toks, c)[0]                      # [ref_len, V]
        nxt = jnp.roll(toks[0], -1)        # the token that followed each position
        top = jnp.max(want, axis=-1)
        chosen = jnp.take_along_axis(want, nxt[:, None], axis=-1)[:, 0]
        spacing = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(jnp.abs(top), 1e-30))) - 7)
        return want, (top - chosen) / spacing

    gaps, rel, per_answer = [], [], []
    for s, g in zip(samples, got):
        prompt, answer = list(s["prompt"]), list(s["answer"])
        toks = np.zeros((1, ref_len), np.int32)
        toks[0, :len(prompt) + len(answer)] = prompt + answer
        want, gap = reference_rows(params, jnp.asarray(toks))
        gap = np.asarray(gap)[len(prompt) - 1: len(prompt) - 1 + len(answer)]
        gaps.extend(gap.tolist())
        errs = {pos: float(ref.rel_err(jnp.asarray(row), want[pos]))
                for pos, row in g.items()}
        prefill = max(e for pos, e in errs.items() if pos < len(prompt))
        decode = max([e for pos, e in errs.items() if pos >= len(prompt)] or [0.0])
        rel.append(max(prefill, decode))
        per_answer.append({"prompt_len": len(prompt), "answer_len": len(answer),
                           "mean_gap_spacings": float(gap.mean()),
                           "off_argmax": int((gap > 0).sum()),
                           "prefill_logits_rel_err": prefill,
                           "decode_logits_rel_err": decode})
    return {"token_gap_mean_spacings": float(np.mean(gaps)),
            "prefill_logits_rel_err": max(rel), "answers": per_answer,
            "tokens_compared": len(gaps)}
