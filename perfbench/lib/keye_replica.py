"""The benchmark's serve replica for Keye-VL-2.0's decoder (sparse-attention
layers whose indexer chooses 2,048 rows a query, each over a scanned expert
layer): the record, clocks, trace annotations and the wrapping of the
engine's entry points are `lib.hybrid_replica.HybridBenchReplica`'s, the
warm-up through the engine's cache interface `lib.granite_replica`'s; what
differs is how the model is built (`lib.keye_model`), what `check` compares
(the reference follows the program's choice of experts AND of rows, over the
prompt and an answer's WHOLE length, and ONE reference pass a sample gives
all four numbers), the `every_row` control
(the program with every causal row chosen: the weights are the seed's, the
model's `dsa_topk` is past every context) and that the trace's reduction
keeps the four `dsa_*` kernels' calls."""

from __future__ import annotations

import re
import threading
import time

from perfbench.lib.granite_replica import _what_is_there
from perfbench.lib.hybrid_replica import HybridBenchReplica
from perfbench.lib.jamba_replica import _kernel_events

STEP_KERNELS = ("dsa_scores", "dsa_rows")


class KeyeBenchReplica(HybridBenchReplica):
    def __init__(self, spec: dict):
        t_enter = time.time()
        import jax
        import jax.numpy as jnp

        from perfbench.lib import keye_model, worker
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
        every_row = spec.get("control") == "every_row"
        self.cfg = cfg = keye_model.model_config(
            spec["config"], **({"dsa_topk": run["max_len"]} if every_row else {}))
        # the comparison's own model: the configuration as published
        self.ref_cfg = keye_model.model_config(spec["config"])
        self.params = keye_model.make_params(cfg, spec["seed"])
        # (`every_row` serves the seed's own weights: nothing is rounded)
        served = self.params if every_row else self._served(self.params)
        if spec.get("control") and not every_row:
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
            lens = jnp.asarray([1], jnp.int32)
            dropped = jnp.asarray([n], jnp.int32)  # out of range
            first, rows = cache.prefill(
                eng.params, jnp.zeros((1, bucket), jnp.int32), lens)
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
        holds, under `kernel_calls`, the prompt kernels' [[positions,
        seconds], ...] an event and the step kernels' [events, seconds].
        Read before the parent's reduction, which removes the trace."""
        from perfbench.lib import xplane

        calls = {}
        if (payload or {}).get("trace"):
            path = xplane.find_xplane(self._trace_dir)
            planes = xplane.load(path)
            rep = self.cfg.n_heads // self.cfg.n_kv_heads
            calls = kernel_calls(planes, rep)
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

        from perfbench.lib import keye_model
        from perfbench.lib.manifest import load_py

        ref = load_py(self.spec["reference_file"])
        tr = self.spec["traffic"]
        t0 = time.time()
        got = program_rows(self.engine, payload["samples"], tr["check_decode_steps"])
        # the reference's 32k positions need the room the slots hold: the
        # engine has served its last (nothing follows `check`)
        self.engine.stop_driver()
        self.engine = None
        gc.collect()     # the instance's wrapped methods point back at it
        params = self.params
        if params is None:   # a control run: remake the seed's weights
            params = keye_model.make_params(self.ref_cfg, self.spec["seed"])
        t1 = time.time()
        # the `every_row` control is compared with the reference's OWN rows
        out = compare_with_reference(
            ref, self.spec["config"], params, payload["samples"], got,
            follow_rows=self.spec.get("control") != "every_row")
        out["check_s"] = {"program_rows": t1 - t0, "reference": time.time() - t1}
        print(f"[check] program rows {t1 - t0:.1f} s, reference "
              f"{time.time() - t1:.1f} s", flush=True)
        return out


def kernel_calls(planes, rep: int) -> dict:
    """The four kernels' device events of a trace. A prompt kernel's event
    is [positions, seconds], the positions from the call's own shape: the
    first `s32[n,1]` of `dsa_select`'s instruction (its thresholds), the
    first `[kvh, n / 256, rep x 256, hd]` of `dsa_attention`'s (its output).
    A step kernel's result says nothing of the slots it served: [events,
    seconds], and the metric takes the rows from the program's counters."""
    out = {}
    shapes = {"dsa_select": (re.compile(r"s32\[(\d+),1\]"),
                             lambda m: int(m.group(1))),
              "dsa_attention": (re.compile(r"\w+\[\d+,(\d+),(\d+),\d+\]"),
                                lambda m: int(m.group(1)) * int(m.group(2)) // rep)}
    for kernel, (shape, positions) in shapes.items():
        found = ((shape.search(op), t) for op, t in _kernel_events(planes, kernel))
        out[kernel] = [[positions(m), t] for m, t in found if m]
    for kernel in STEP_KERNELS:
        events = _kernel_events(planes, kernel)
        out[kernel] = [len(events), sum(t for _, t in events)]
    return out


def program_rows(engine, samples, decode_steps):
    """What the ENGINE that served the window computes for each sample, as
    numpy, through its own slot state after the window (nothing is live any
    more; the stepper is held off).

    Prefill: every sample's whole prompt and its first half are admitted the
    way `_dispatch_prefill` admits, one prompt a call, both at the bucket
    admission gives the whole prompt (`hybrid.prefill(with_routing=
    True)`: admission's program with the logits, every position's choice of
    experts and every query's choice of rows returned as well). The state
    rows of the whole prompts go into slots of the engine's cache, spread
    over it, by the engine's own `cache.prefill` and `cache.write`.

    Decode: the samples, all live at once among the engine's idle slots, are
    decoded `decode_steps` tokens (no more than the longest sampled answer
    has; the cell's traffic file asks for an answer's WHOLE length, so that
    the reference can follow the program at every token the window served),
    teacher-forced, by `hybrid.decode_logits`: the step program's body over
    the engine's donated state, as `_dispatch_decode` runs it, with each
    slot's row list returned as well.

    -> per sample {"rows": {position: logits}, "routing": [layers, prompt +
    decode_steps, k], "chosen": [layers, ceil(bucket / 32), bucket] int32
    (the whole prompt's pass: `ops.dsa.pack_rows`), "bucket", "lists": per
    decoded position (rows [layers, K], count [layers], own [layers])}."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import hybrid
    from ray_tpu.models.serving import _attn_bucket

    cfg, served, cache = engine.cfg, engine.params, engine.cache
    B, max_len = engine.num_slots, engine.max_len
    spread = [(3 * j + 1) % B for j in range(B)] if B % 3 else list(range(B))
    out = [{"rows": {}, "routing": [], "lists": []} for _ in samples]

    @functools.partial(jax.jit, static_argnames=("cfg",))
    def choices(p, toks, lens, cfg):
        # admission's program with what it chose; the state rows it would
        # return are dropped here (a 32k prompt's are 0.45 GB beside the
        # 0.8 GB of its chosen rows): the engine's own pass brings them
        logits, rows = hybrid.prefill(p, toks, lens, cfg, with_routing=True)
        return logits, rows["routing"], rows.get("chosen")

    with engine._step_lock:
        slot = {i: spread[i] for i in range(len(samples))}
        for i, s in enumerate(samples):
            n = len(s["prompt"])
            # the half rides in the bucket of the whole: the reference follows
            # the choices of the whole prompt's pass, and only the same
            # program turns every near-tie the same way (a pass of its own
            # bucket read 0.080 where the whole's read 0.006: call 4, s09)
            bucket = cache.prompt_bucket(n)
            for upto in sorted({n, max(1, n // 2)}, reverse=True):
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :upto] = s["prompt"][:upto]
                toks, lens = jnp.asarray(toks), jnp.asarray([upto], jnp.int32)
                logits, routing, chosen = choices(served, toks, lens, cfg)
                out[i]["rows"][upto - 1] = np.asarray(logits[0])
                if upto == n:
                    out[i]["routing"].append(np.asarray(routing)[:, 0, :n])
                    out[i]["bucket"] = bucket
                    out[i]["chosen"] = None if chosen is None \
                        else np.asarray(chosen[:, 0])
                    del logits, routing, chosen
                    first, rows = cache.prefill(served, toks, lens)
                    engine.lengths, engine.tokens = cache.write(
                        engine.lengths, engine.tokens,
                        jnp.asarray([slot[i]], jnp.int32), rows, lens, first)
                    del rows
        longest = max(len(s["prompt"]) for s in samples)
        decode_steps = min(decode_steps, max(len(s["answer"]) for s in samples))
        attn_len = _attn_bucket(longest + decode_steps, max_len)
        for t in range(decode_steps):
            toks = np.zeros((B,), np.int32)
            for i, s in enumerate(samples):
                toks[slot[i]] = (s["answer"][t:t + 1] or [0])[0]
            cache.state, logits, chose, lists = hybrid.decode_logits(
                served, cache.state, engine.lengths, jnp.asarray(toks), None,
                cfg, attn_len)
            engine.lengths = engine.lengths + (engine.lengths > 0)
            logits, chose = np.asarray(logits), np.asarray(chose)
            lists = [np.asarray(a) for a in lists]
            for i, s in enumerate(samples):
                if t < len(s["answer"]):
                    out[i]["rows"][len(s["prompt"]) + t] = logits[slot[i]]
                    out[i]["routing"].append(chose[:, slot[i]][:, None])
                    out[i]["lists"].append(tuple(a[:, slot[i]] for a in lists))
        engine.lengths = jnp.zeros((B,), jnp.int32)
        engine.tokens = jnp.zeros((B,), jnp.int32)
    for o in out:
        o["routing"] = np.concatenate(o["routing"], axis=1)
    return out


def chosen_words(g, n_prompt: int, ref_len: int):
    """One sample's choice of rows for the reference, [layers, ref_len / 32,
    ref_len] int32 (`ops.dsa.pack_rows`' words): the prompt pass's words for
    the prompt's queries (a prompt of no more than topk positions brings
    none: every row is chosen), the decode steps' lists for the decoded
    positions (a listed row, and the position's own where it belongs to the
    best), and everywhere else bits all set (every causal row: what a
    prompt of no more than topk positions chose; behind the decoded
    positions the reference follows nothing)."""
    import numpy as np

    layers, bucket = g["lists"][0][0].shape[0], g["bucket"]
    words = np.full((layers, ref_len // 32, ref_len), -1, np.int32)
    if g["chosen"] is not None:
        rows_w, cols = min(g["chosen"].shape[1], ref_len // 32), min(bucket, ref_len)
        words[:, :rows_w, :cols] = g["chosen"][:, :rows_w, :cols]
    bits = words.view(np.uint32)
    for t, (rows, count, own) in enumerate(g["lists"]):
        pos = n_prompt + t
        w, bit = pos // 32, np.uint32(1 << (pos % 32))
        bits[:, w, :] &= ~bit
        for layer in range(layers):
            listed = rows[layer, :count[layer]]
            bits[layer, w, listed] |= bit
            if own[layer]:
                bits[layer, w, pos] |= bit
    return words


def compare_with_reference(ref, c, params, samples, got,
                           follow_rows: bool = True) -> dict:
    """The plain float32 reference against what was served, four numbers,
    from ONE reference pass a sample over prompt + answer, padded to whole
    blocks of eight chunks (4,096 positions: the prompt's own bucket or the
    next; every sample of a run at the longest's, ONE program), the
    reference following the PROGRAM's choice of experts and of rows at the
    positions `program_rows` reports (the prompt and the decoded positions)
    and its own behind them.

    `token_gap_mean_spacings`, `prefill_logits_rel_err`, `route_margin_max`:
    `lib.granite_replica.compare_with_reference` says what they are. The
    first is the one number that reads the tokens the WINDOW served (the
    timed step program's own output, other slots live): it means something
    only where the reference follows the program at every served token,
    because with seeded weights a query's softmax over its 2,048 rows is
    nearly flat and a reference that picks its own rows behind the followed
    positions computes another function there, whatever the precision.

    `select_margin_max`: what keeps following the program's choice of rows
    honest: how far, at worst, a row the program chose scores (the
    indexer's score in the reference's own arithmetic) under the
    reference's own 2,048th best of that query, in standard deviations of
    the query's causal score row. A near-tie is hundredths; an indexer
    computed in a lower precision, or on the wrong rows, is tenths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    answer_max = max(len(s["answer"]) for s in samples)
    n_rows = max(len(g["rows"]) for g in got)

    @jax.jit
    def tail(p, feats, toks, first, at):
        # the answer's rows (a fixed count from `first`) and the compared rows
        span = jax.lax.dynamic_slice_in_dim(feats[0], first, answer_max, axis=0)
        want = ref.head(p, span, c)
        nxt = jax.lax.dynamic_slice_in_dim(jnp.roll(toks[0], -1), first, answer_max)
        top = jnp.max(want, axis=-1)
        picked = jnp.take_along_axis(want, nxt[:, None], axis=-1)[:, 0]
        spacing = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(jnp.abs(top), 1e-30))) - 7)
        return (top - picked) / spacing, ref.head(p, feats[0][at], c)

    def reference(p, toks, routing, chosen, followed, first, at):
        # the stack a layer a program (`features_routed` says why), then the head
        feats, route_worst, select_worst = ref.features_routed(
            p, toks, c, routing, chosen, followed)
        return tail(p, feats, toks, first, at) + (route_worst, select_worst)

    layers, _, k = got[0]["routing"].shape
    gaps, rel, routes, selects, per_answer = [], [], [], [], []
    unit = 8 * c["sa_config"]["q_chunk_size"]
    ref_len = -(-max(len(s["prompt"]) + len(s["answer"]) for s in samples)
                // unit) * unit
    for s, g in zip(samples, got):
        prompt, answer = list(s["prompt"]), list(s["answer"])
        toks = np.zeros((1, ref_len), np.int32)
        toks[0, :len(prompt) + len(answer)] = prompt + answer
        n = g["routing"].shape[1]
        routing = np.full((layers, 1, ref_len, k), -1, np.int32)  # -1: free
        routing[:, 0, :n] = g["routing"]
        words = chosen_words(g, len(prompt), ref_len) if follow_rows else None
        at = sorted(g["rows"])
        first = min(len(prompt) - 1, ref_len - answer_max)
        gap, want, route_worst, select_worst = reference(
            params, jnp.asarray(toks), jnp.asarray(routing),
            None if words is None else jnp.asarray(words[:, None]),
            len(prompt) + len(g["lists"]), jnp.asarray(first, jnp.int32),
            jnp.asarray(at + [0] * (n_rows - len(at)), jnp.int32))
        lo = len(prompt) - 1 - first
        gap = np.asarray(gap)[lo:lo + len(answer)]
        gaps.extend(gap.tolist())
        each = jax.vmap(ref.rel_err)(
            jnp.asarray(np.stack([g["rows"][pos] for pos in at])), want[:len(at)])
        errs = dict(zip(at, np.asarray(each).tolist()))
        prefill = max(e for pos, e in errs.items() if pos < len(prompt))
        decode = max([e for pos, e in errs.items() if pos >= len(prompt)] or [0.0])
        rel.append(max(prefill, decode))
        routes.append(float(route_worst))
        selects.append(float(select_worst))
        per_answer.append({"prompt_len": len(prompt), "answer_len": len(answer),
                           "mean_gap_spacings": float(gap.mean()),
                           "off_argmax": int((gap > 0).sum()),
                           "prefill_logits_rel_err": prefill,
                           "decode_logits_rel_err": decode,
                           "route_margin": float(route_worst),
                           "select_margin": float(select_worst)})
    return {"token_gap_mean_spacings": float(np.mean(gaps)),
            "prefill_logits_rel_err": max(rel), "route_margin_max": max(routes),
            "select_margin_max": max(selects),
            "answers": per_answer, "tokens_compared": len(gaps)}
