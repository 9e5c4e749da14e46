"""The benchmark's serve replica for Command A+'s language model (window and
full attention layers 3 : 1, each over a scanned expert layer beside the
mean of the shared experts): the record, clocks, trace annotations and the
wrapping of the engine's entry points are
`lib.hybrid_replica.HybridBenchReplica`'s, the reference's three numbers
`lib.granite_replica.compare_with_reference`'s; what differs is how the
model is built (`lib.cmda_model`), WHICH answers `check` compares (of every
answer the window served: one short one and one long one whose prompt lies
past three windows, so that the ring has wrapped in the prompt pass and
wraps in every decoded position; the prompt pass and EVERY position of the
answer through the engine's own slot state with other slots live), the
`no_window` control (the program with every layer full, the weights the
seed's, fewer slots so that four full layers' rows fit) and that the trace's
reduction keeps the banded prompt kernel's and the decode kernel's calls."""

from __future__ import annotations

import threading
import time

from perfbench.lib.granite_replica import _what_is_there, compare_with_reference
from perfbench.lib.hybrid_replica import HybridBenchReplica
from perfbench.lib.jamba_replica import _kernel_events

KERNELS = ("flash_attention_banded", "gqa_decode_attention")
STEP_SPAN = "bench.engine_step"     # `BenchReplica._wrap`: in the trace and on the wall clock
NO_WINDOW_SLOTS = 4     # 4 slots x 4 full layers x 49,152 rows = 3.2 GB


class CmdaBenchReplica(HybridBenchReplica):
    def __init__(self, spec: dict):
        t_enter = time.time()
        import jax
        import jax.numpy as jnp

        from perfbench.lib import cmda_model, worker
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
        no_window = spec.get("control") == "no_window"
        # the comparison's own model: the configuration as published
        self.ref_cfg = cmda_model.model_config(spec["config"])
        self.cfg = cfg = cmda_model.model_config(
            spec["config"], swa_layers=(),
            full_layers=tuple(range(1, self.ref_cfg.n_layers + 1))) \
            if no_window else self.ref_cfg
        # (`no_window` serves weights of its own stack's making, every one of
        # the seed's drawing: `check` hands the SAME weights to the reference,
        # cut into the published runs)
        self.params = cmda_model.make_params(cfg, spec["seed"])
        served = self.params if no_window else self._served(self.params)
        if spec.get("control") and not no_window:
            # the control keeps the rounded weights (donated) and `check`
            # makes the seed's again once the engine is gone
            self.params = None
        slots = min(run["num_slots"], NO_WINDOW_SLOTS) if no_window else run["num_slots"]
        self.engine = eng = ContinuousBatchingEngine(
            served, cfg, num_slots=slots, max_len=run["max_len"])
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
        holds, under `kernel_calls`, each kernel's [events, seconds], and
        under `prompt_kernel_events` the prompt kernel's events one by one,
        on the wall clock. Read before the parent's reduction, which removes
        the trace."""
        from perfbench.lib import xplane

        calls, events = {}, []
        if (payload or {}).get("trace"):
            path = xplane.find_xplane(self._trace_dir)
            planes = xplane.load(path)
            calls = kernel_calls(planes)
            events = wall_clock_events(
                planes, self.spans.rows.get(STEP_SPAN, []), KERNELS[0])
            if not any(k.startswith("/device:") for k in planes):
                print(f"[trace] no device plane in {path}: {_what_is_there(path)}",
                      flush=True)
        out = super().stats(payload)
        if out.get("trace"):
            out["trace"]["kernel_calls"] = calls
            out["trace"]["prompt_kernel_events"] = events
        return out

    # --------------------------------------------------------- correctness
    def check(self, payload):
        import gc

        from perfbench.lib import cmda_model
        from perfbench.lib.manifest import load_py

        ref = load_py(self.spec["reference_file"])
        tr = self.spec["traffic"]
        samples = choose_samples(payload["samples"], self.ref_cfg.swa_window)
        t0 = time.time()
        got = program_rows(self.engine, samples, tr["check_decode_steps"])
        # the reference's tens of thousands of positions need the room the
        # slots hold: the engine has served its last (nothing follows `check`)
        self.engine.stop_driver()
        self.engine = None
        gc.collect()     # the instance's wrapped methods point back at it
        params, self.params = self.params, None
        if params is None:   # a control run: remake the seed's weights
            params = cmda_model.make_params(self.ref_cfg, self.spec["seed"])
        elif self.spec.get("control") == "no_window":
            params["runs"] = as_published(params["runs"][0], self.ref_cfg.runs())
        t1 = time.time()
        # one sample after another, each at its own length (whole blocks of
        # 2,048 positions): the long one's pass would cost the short one 8x
        outs = [compare_with_reference(
            ref, self.spec["config"], params, [s], [g],
            -(-(len(s["prompt"]) + len(s["answer"])) // 2048) * 2048)
            for s, g in zip(samples, got)]
        n = [o["tokens_compared"] for o in outs]
        out = {"token_gap_mean_spacings": sum(
                   o["token_gap_mean_spacings"] * k for o, k in zip(outs, n)) / sum(n),
               "prefill_logits_rel_err": max(o["prefill_logits_rel_err"] for o in outs),
               "route_margin_max": max(o["route_margin_max"] for o in outs),
               "answers": [a for o in outs for a in o["answers"]],
               "tokens_compared": sum(n), "answers_offered": len(payload["samples"]),
               "check_s": {"program_rows": t1 - t0, "reference": time.time() - t1}}
        print(f"[check] program rows {t1 - t0:.1f} s, reference "
              f"{time.time() - t1:.1f} s", flush=True)
        return out


def as_published(run, runs):
    """ONE run of full layers (the `no_window` control's stack, every leaf
    stacked on its leading axis) cut into the published `runs` [(kind,
    count)], a leaf at a time: each whole leaf is dropped as its parts
    exist, so the cut needs the room of one leaf, not of the model."""
    bounds, at = [], 0
    for _, k in runs:
        bounds.append((at, at + k))
        at += k

    def cut(tree):
        if not isinstance(tree, dict):
            return [tree[a:b] for a, b in bounds]
        parts = [{} for _ in bounds]
        for key in list(tree):
            for part, sub in zip(parts, cut(tree.pop(key))):
                part[key] = sub
        return parts

    return [{(kind if key == "full" else key): v for key, v in part.items()}
            for part, (kind, _) in zip(cut(run), runs)]


def kernel_calls(planes) -> dict:
    """[events, seconds] of the prompt pass's banded kernel and of the decode
    step's kernel in a trace. Neither call's shape says how many rows it
    read (the chunk a pass stands at and the slots' lengths are data): the
    metrics take those from the program's spans and counters."""
    out = {}
    for kernel in KERNELS:
        events = _kernel_events(planes, kernel)
        out[kernel] = [len(events), sum(t for _, t in events)]
    return out


def wall_clock_events(planes, steps, kernel: str) -> list:
    """[[start, seconds], ...] of one kernel's device events, the start on
    the WALL clock (seconds since the epoch), so that a reader can tell which
    of the program's spans an event ran under. A trace counts from its own
    start; the host's `STEP_SPAN` events are in it AND, as `steps` [(start,
    end, ...)], on the wall clock (`worker.Spans`), the traced ones a
    contiguous stretch of `steps`: the stretch whose durations fit the
    trace's best gives the trace's start. [] without a device plane or
    without steps in the trace."""
    import numpy as np

    from perfbench.lib import xplane

    traced = sorted((start, dur) for name, plane in planes.items()
                    if name.startswith("/host:") for line in plane.values()
                    for op, start, dur in line if op == STEP_SPAN)
    events = [(start, dur) for name, plane in planes.items()
              if name.startswith("/device:")
              for op, start, dur in plane.get(xplane.OPS_LINE, [])
              if kernel in op.split("=")[0]]
    if not events or not traced or len(steps) < len(traced):
        return []
    took = np.asarray([b - a for a, b, *_ in steps])
    fits = np.abs(np.lib.stride_tricks.sliding_window_view(took, len(traced))
                  - np.asarray([d for _, d in traced]) / 1e9).sum(axis=1)
    began = steps[int(np.argmin(fits))][0] - traced[0][0] / 1e9
    return [[began + start / 1e9, dur / 1e9] for start, dur in sorted(events)]


def choose_samples(samples, window: int):
    """Of every answer the window served: the short one of median prompt
    length (a prompt inside one window) and the long one whose prompt is the
    shortest past THREE windows (the longest there is, if none is): the ring
    has then wrapped in its prompt pass and wraps at every decoded position,
    and the reference's pass stays as short as that allows."""
    short = sorted((s for s in samples if len(s["prompt"]) <= window),
                   key=lambda s: len(s["prompt"]))
    long_ = sorted((s for s in samples if len(s["prompt"]) > window),
                   key=lambda s: len(s["prompt"]))
    past = [s for s in long_ if len(s["prompt"]) > 3 * window]
    picked = short[len(short) // 2:len(short) // 2 + 1] + (past[:1] or long_[-1:])
    return picked or samples[:1]


def program_rows(engine, samples, decode_steps):
    """What the ENGINE that served the window computes for each sample, as
    numpy, through its own slot state after the window (nothing is live any
    more; the stepper is held off).

    Prefill: every sample's whole prompt and its first half are admitted the
    way `_dispatch_prefill` admits, one prompt a call, both at the bucket
    admission gives the whole prompt (`hybrid.prefill(with_routing=True)`:
    admission's program with the logits and every position's choice of
    experts returned as well; a long prompt's pass walks its chunks). The
    state rows of the whole prompts go into slots of the engine's cache,
    spread over it, by the engine's own `cache.write`; the first sample's
    rows go into two more slots besides, so that other slots are live.

    Decode: all of them live at once among the engine's idle slots, the
    samples are decoded `decode_steps` tokens (no more than the longest
    sampled answer has), teacher-forced, by `hybrid.decode_logits`: the step
    program's body over the engine's donated state, at the attention length
    the timed step is built for (`cache.step_len`: the slot's whole length
    where the decode kernel runs, so the kernel's blocks and its walk over
    the live rows are the timed step's), as `_dispatch_decode` runs it.

    -> per sample {"rows": {position: logits}, "routing": [layers,
    prompt + decode_steps, k]}."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import hybrid
    from ray_tpu.models.serving import _attn_bucket

    cfg, served, cache = engine.cfg, engine.params, engine.cache
    B, max_len = engine.num_slots, engine.max_len
    spread = [(5 * j + 1) % B for j in range(B)] if B % 5 else list(range(B))
    out = [{"rows": {}, "routing": []} for _ in samples]
    with engine._step_lock:
        slot = {i: spread[i] for i in range(len(samples))}
        fillers = spread[len(samples):len(samples) + 2]
        for i, s in enumerate(samples):
            n = len(s["prompt"])
            bucket = cache.prompt_bucket(n)
            for upto in sorted({n, max(1, n // 2)}, reverse=True):
                toks = np.zeros((1, bucket), np.int32)
                toks[0, :upto] = s["prompt"][:upto]
                lens = jnp.asarray([upto], jnp.int32)
                logits, rows = hybrid.prefill(served, jnp.asarray(toks), lens, cfg,
                                              with_routing=True)
                routing = np.asarray(rows.pop("routing"))
                out[i]["rows"][upto - 1] = np.asarray(logits[0])
                if upto < n:
                    continue
                out[i]["routing"].append(routing[:, 0, :n])
                for to in [slot[i]] + (fillers if i == 0 else []):
                    engine.lengths, engine.tokens = cache.write(
                        engine.lengths, engine.tokens, jnp.asarray([to], jnp.int32),
                        rows, lens, jnp.zeros((1,), jnp.int32))
        longest = max(len(s["prompt"]) for s in samples)
        decode_steps = min(decode_steps, max(len(s["answer"]) for s in samples))
        attn_len = cache.step_len(_attn_bucket(longest + decode_steps, max_len))
        for t in range(decode_steps):
            toks = np.zeros((B,), np.int32)
            for i, s in enumerate(samples):
                toks[slot[i]] = (s["answer"][t:t + 1] or [0])[0]
            toks[fillers] = toks[slot[0]]
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
