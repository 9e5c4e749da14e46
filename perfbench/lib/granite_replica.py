"""The benchmark's serve replica for the Granite-4.0-H model (Mamba-2 and
attention mixers as scanned runs, an expert layer in every layer): the
record, clocks, trace annotations, wrapping of the engine's entry points and
warm-up through the engine's cache interface are
`lib.hybrid_replica.HybridBenchReplica`'s; what differs is how the model is
built (`lib.granite_model`), what `check` compares (the reference follows
the program's choice of experts, as the Kimi cell's does, and ONE reference
pass a sample gives all three numbers: it is 13056 positions long), and that
the trace's reduction keeps the `ssd_step` kernel's calls."""

from __future__ import annotations

import threading
import time

from perfbench.lib.hybrid_replica import HybridBenchReplica
from perfbench.lib.jamba_replica import _kernel_events

STEP_KERNEL = "ssd_step"


class GraniteBenchReplica(HybridBenchReplica):
    def __init__(self, spec: dict):
        t_enter = time.time()
        import jax
        import jax.numpy as jnp

        from perfbench.lib import granite_model, worker
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
        self.cfg = cfg = granite_model.model_config(spec["config"])
        self.params = granite_model.make_params(cfg, spec["seed"])
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
        holds, under `kernel_calls`, the `ssd_step` kernel's [events,
        seconds]. Read before the parent's reduction, which removes the trace."""
        from perfbench.lib import xplane

        calls = {}
        if (payload or {}).get("trace"):
            path = xplane.find_xplane(self._trace_dir)
            planes = xplane.load(path)
            calls = {STEP_KERNEL: step_calls(planes)}
            if not any(k.startswith("/device:") for k in planes):
                print(f"[trace] no device plane in {path}: {_what_is_there(path)}",
                      flush=True)
        out = super().stats(payload)
        if out.get("trace"):
            out["trace"]["kernel_calls"] = calls
        return out

    # --------------------------------------------------------- correctness
    def check(self, payload):
        import gc

        from perfbench.lib import granite_model
        from perfbench.lib.manifest import load_py

        ref = load_py(self.spec["reference_file"])
        tr = self.spec["traffic"]
        t0 = time.time()
        got = program_rows(self.engine, payload["samples"], tr["check_decode_steps"])
        # the reference's 13056 positions need the room the slots hold: the
        # engine has served its last (nothing follows `check`)
        self.engine.stop_driver()
        self.engine = None
        gc.collect()     # the instance's wrapped methods point back at it
        params = self.params
        if params is None:   # a control run: remake the seed's weights
            params = granite_model.make_params(self.cfg, self.spec["seed"])
        t1 = time.time()
        out = compare_with_reference(
            ref, self.spec["config"], params, payload["samples"], got,
            tr["prompt_tokens"]["max"] + tr["answer_tokens"]["max"])
        out["check_s"] = {"program_rows": t1 - t0, "reference": time.time() - t1}
        print(f"[check] program rows {t1 - t0:.1f} s, reference "
              f"{time.time() - t1:.1f} s", flush=True)
        return out


def _what_is_there(path: str) -> str:
    """Size of a trace file and every plane and line in it with its number
    of events: what to look at when the reduction finds no device plane."""
    import os

    import jax

    data = jax.profiler.ProfileData.from_file(path)
    lines = [f"{p.name}/{l.name}: {sum(1 for _ in l.events)}"
             for p in data.planes for l in p.lines]
    return f"{os.path.getsize(path)} bytes; " + "; ".join(lines[:40])


def step_calls(planes) -> list:
    """[events, seconds] of the `ssd_step` kernel: one event a Mamba-2 layer
    and decode step (its result is the whole stacked state, so the shape
    says nothing of the slots it touched: the metric takes those from the
    program's `state_slots`)."""
    events = _kernel_events(planes, STEP_KERNEL)
    return [len(events), sum(t for _, t in events)]


def program_rows(engine, samples, decode_steps):
    """What the ENGINE that served the window computes for each sample, as
    numpy, through its own slot state after the window (nothing is live any
    more; the stepper is held off).

    Prefill: every sample's whole prompt and its first half are admitted the
    way `_dispatch_prefill` admits, one prompt a call, at two of the buckets
    the window used: the bucket of the longest prompt and the smallest
    bucket that holds a third of it (`hybrid.prefill(with_routing=True)`:
    admission's program with the logits and every position's choice of
    experts returned as well). The state rows of the whole prompts go into
    slots of the engine's cache, spread over it, by the engine's own
    `cache.write`.

    Decode: the samples, all live at once among the engine's idle slots, are
    decoded `decode_steps` tokens, teacher-forced, by `hybrid.decode_logits`:
    the step program's body over the engine's donated state, as
    `_dispatch_decode` runs it.

    -> per sample {"rows": {position: logits}, "routing": [layers,
    prompt + decode_steps, k]}."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import hybrid
    from ray_tpu.models.serving import _attn_bucket

    cfg, served, cache = engine.cfg, engine.params, engine.cache
    B, max_len = engine.num_slots, engine.max_len
    longest = max(len(s["prompt"]) for s in samples)
    buckets = sorted({cache.prompt_bucket(max(1, longest // 3)),
                      cache.prompt_bucket(longest)})
    spread = [(7 * j + 3) % B for j in range(B)] if B % 7 else list(range(B))
    out = [{"rows": {}, "routing": []} for _ in samples]
    with engine._step_lock:
        slot = {i: spread[i] for i in range(len(samples))}
        for i, s in enumerate(samples):
            n = len(s["prompt"])
            # the half rides in the bucket of the whole: the reference follows
            # the routing of the whole prompt's pass
            bucket = next(b for b in buckets if b >= n)
            for upto in sorted({n, max(1, n // 2)}, reverse=True):
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :upto] = s["prompt"][:upto]
                lens = jnp.asarray([upto], jnp.int32)
                logits, rows = hybrid.prefill(served, jnp.asarray(toks), lens, cfg,
                                              with_routing=True)
                routing = np.asarray(rows.pop("routing"))
                out[i]["rows"][upto - 1] = np.asarray(logits[0])
                if upto == n:
                    out[i]["routing"].append(routing[:, 0, :n])
                engine.lengths, engine.tokens = cache.write(
                    engine.lengths, engine.tokens,
                    jnp.asarray([slot[i] if upto == n else B], jnp.int32),  # B: dropped
                    rows, lens, jnp.zeros((1,), jnp.int32))
        attn_len = _attn_bucket(longest + decode_steps, max_len)
        for t in range(decode_steps):
            toks = np.zeros((B,), np.int32)
            for i, s in enumerate(samples):
                toks[slot[i]] = (s["answer"][t:t + 1] or [0])[0]
            cache.state, logits, chose = hybrid.decode_logits(
                served, cache.state, engine.lengths, jnp.asarray(toks), None,
                cfg, attn_len)
            engine.lengths = engine.lengths + (engine.lengths > 0)
            logits, chose = np.asarray(logits), np.asarray(chose)
            for i, s in enumerate(samples):
                if t < len(s["answer"]):
                    out[i]["rows"][len(s["prompt"]) + t] = logits[slot[i]]
                    out[i]["routing"].append(chose[:, slot[i]][:, None])
        engine.lengths = jnp.zeros((B,), jnp.int32)
        engine.tokens = jnp.zeros((B,), jnp.int32)
    for o in out:
        o["routing"] = np.concatenate(o["routing"], axis=1)
    return out


def compare_with_reference(ref, c, params, samples, got, ref_len) -> dict:
    """The plain float32 reference against what was served, three numbers,
    from ONE reference pass a sample over prompt + answer (one fixed shape,
    `ref_len` positions), the reference following the PROGRAM's choice of
    experts at the positions `program_rows` reports (the prompt and the
    decoded positions) and its own behind them.

    `token_gap_mean_spacings`: for every token the engine chose, how far the
    reference's logit of it lies under the reference's top logit, in bf16
    spacings of that logit (with random weights the top two are often a
    rounding apart, so tokens are not compared; a lower precision pushes the
    mean gap up).

    `prefill_logits_rel_err`: the largest relative error, over the samples,
    of the program's logits (`program_rows`: prefill as admission runs it,
    then decode through the engine's slot state) against the reference's
    at the same positions.

    `route_margin_max`: what keeps following the program's choice honest:
    how far, at worst, an expert the program chose scores (its logit, in the
    reference's own arithmetic) under the reference's k-th best. A near-tie
    is hundredths; a router computed in bf16, or on a wrongly scaled stream,
    is tenths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    answer_max = max(len(s["answer"]) for s in samples)
    n_rows = max(len(g["rows"]) for g in got)

    @jax.jit
    def reference(p, toks, routing, first, at):
        feats, worst = ref.features_routed(p, toks, c, routing)
        # the answer's rows (a fixed count from `first`) and the compared rows
        span = jax.lax.dynamic_slice_in_dim(feats[0], first, answer_max, axis=0)
        want = ref.head(p, span, c)
        nxt = jax.lax.dynamic_slice_in_dim(jnp.roll(toks[0], -1), first, answer_max)
        top = jnp.max(want, axis=-1)
        chosen = jnp.take_along_axis(want, nxt[:, None], axis=-1)[:, 0]
        spacing = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(jnp.abs(top), 1e-30))) - 7)
        return (top - chosen) / spacing, ref.head(p, feats[0][at], c), worst

    layers, _, k = got[0]["routing"].shape
    gaps, rel, margins, per_answer = [], [], [], []
    for s, g in zip(samples, got):
        prompt, answer = list(s["prompt"]), list(s["answer"])
        toks = np.zeros((1, ref_len), np.int32)
        toks[0, :len(prompt) + len(answer)] = prompt + answer
        n = g["routing"].shape[1]
        routing = np.full((layers, 1, ref_len, k), -1, np.int32)  # -1: free
        routing[:, 0, :n] = g["routing"]
        at = sorted(g["rows"])
        first = min(len(prompt) - 1, ref_len - answer_max)
        gap, want, worst = reference(
            params, jnp.asarray(toks), jnp.asarray(routing), first,
            jnp.asarray(at + [0] * (n_rows - len(at)), jnp.int32))
        lo = len(prompt) - 1 - first
        gap = np.asarray(gap)[lo:lo + len(answer)]
        gaps.extend(gap.tolist())
        errs = {pos: float(ref.rel_err(jnp.asarray(g["rows"][pos]), want[j]))
                for j, pos in enumerate(at)}
        prefill = max(e for pos, e in errs.items() if pos < len(prompt))
        decode = max([e for pos, e in errs.items() if pos >= len(prompt)] or [0.0])
        rel.append(max(prefill, decode))
        margins.append(float(worst))
        per_answer.append({"prompt_len": len(prompt), "answer_len": len(answer),
                           "mean_gap_spacings": float(gap.mean()),
                           "off_argmax": int((gap > 0).sum()),
                           "prefill_logits_rel_err": prefill,
                           "decode_logits_rel_err": decode,
                           "route_margin": float(worst)})
    return {"token_gap_mean_spacings": float(np.mean(gaps)),
            "prefill_logits_rel_err": max(rel), "route_margin_max": max(margins),
            "answers": per_answer, "tokens_compared": len(gaps)}
