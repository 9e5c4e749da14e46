"""The benchmark's serve replica for SmallThinker-21BA3B (global and window
attention layers 1 : 3, each over a scanned expert layer whose route was
made from the attention's input): the record, clocks, trace annotations, the
wrapping of the engine's entry points and the trace's reduction (the banded
prompt kernel's and the decode kernel's calls) are
`lib.cmda_replica.CmdaBenchReplica`'s, the replay through the engine's own
slot state `lib.cmda_replica.program_rows`, the reference's three numbers
`lib.granite_replica.compare_with_reference`'s; what differs is how the
model is built (`lib.sthink_model`) and WHICH answers `check` compares: of
every answer the window served, one whose context crosses the window WHILE
it is decoded (the ring wraps during the replayed steps and the row at n mod
W leaves from then on) and one whose prompt lies past the window (a walked
prompt pass; the ring wrapped from its first step), ONE reference pass of one
length for both."""

from __future__ import annotations

import os
import shutil
import threading
import time

from perfbench.lib.cmda_replica import CmdaBenchReplica, program_rows
from perfbench.lib.granite_replica import compare_with_reference

# Controls that are no lower precision but a FAULT of the block, planted in
# the program that serves the window (the seed's weights, one property of the
# configuration another; `check` compares with the reference as published):
# what `token_gap_mean_spacings`, the one compared number read off the tokens
# the window itself served, reads when the timed step computes another model.
FAULTS = {"late_route": {"route_from": "ffn"},    # the router reads the FFN's input
          "silu_gate": {"gate_act": "silu"}}      # SwiGLU experts


class SthinkBenchReplica(CmdaBenchReplica):
    def __init__(self, spec: dict):
        t_enter = time.time()
        import jax
        import jax.numpy as jnp

        from perfbench.lib import sthink_model, worker
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
        fault = FAULTS.get(spec.get("control"))
        self.cfg = cfg = sthink_model.model_config(spec["config"], **(fault or {}))
        self.params = sthink_model.make_params(cfg, spec["seed"])
        served = self.params if fault else self._served(self.params)
        if spec.get("control") and not fault:
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

    # --------------------------------------------------------------- trace
    def trace_between(self, payload):
        """`BenchReplica.trace_between` with the profiler's PYTHON tracer off
        (it records every call of every thread, and this cell streams 430
        tokens a second through Python). What the profiler costs this cell,
        read with it off (my chip runs, PR 63, calls 1-3): ~14 s to WRITE each
        traced second (three seconds were written 47 s after they ended, five
        69 s; ten were not within 160 s, with the Python tracer on or off,
        and `BenchReplica.trace_stop` gave the run up), so the traffic file
        traces four. The host's `TraceAnnotation`s (`bench.*`), which the reduction reads
        beside the device's planes, are the host tracer's and stay."""
        self._trace_dir = os.path.join(self.spec["out_dir"], "trace")
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        self._trace_times = {}

        def trace():
            import jax

            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            time.sleep(max(0.0, payload["start"] - time.time()))
            jax.profiler.start_trace(self._trace_dir, profiler_options=options)
            self._trace_times["started"] = time.time()
            time.sleep(max(0.0, payload["stop"] - time.time()))
            self._trace_times["stopped"] = time.time()
            jax.profiler.stop_trace()   # returns once the trace is written
            self._trace_times["written"] = time.time()

        self._tracer = threading.Thread(target=trace, daemon=True)
        self._tracer.start()
        return {}

    def trace_stop(self, _payload=None):
        """`BenchReplica.trace_stop` with 270 s of patience where it has 120
        (the driver's call allows 300): the first traced run of a fresh
        machine wrote its four seconds 133 s after the window opened, 80 s
        behind the last answer (my chip run, PR 63, call 9; 55-90 s in the
        five others), and a trace given up is a run lost."""
        self._tracer.join(timeout=150.0)
        return super().trace_stop()

    # --------------------------------------------------------- correctness
    def check(self, payload):
        import gc

        from perfbench.lib import sthink_model
        from perfbench.lib.manifest import load_py

        ref = load_py(self.spec["reference_file"])
        tr = self.spec["traffic"]
        samples = choose_samples(payload["samples"], self.cfg.swa_window,
                                 tr["check_decode_steps"])
        t0 = time.time()
        got = program_rows(self.engine, samples, tr["check_decode_steps"])
        # the reference's thousands of positions at the published widths need
        # the room the slots hold: the engine has served its last
        self.engine.stop_driver()
        self.engine = None
        gc.collect()     # the instance's wrapped methods point back at it
        params, self.params = self.params, None
        if params is None:   # a control run: remake the seed's weights
            params = sthink_model.make_params(self.cfg, self.spec["seed"])
        t1 = time.time()
        # ONE length for both samples (whole blocks of 2,048 positions): one
        # program of the reference, compiled once
        ref_len = -(-max(len(s["prompt"]) + len(s["answer"]) for s in samples)
                    // 2048) * 2048
        out = compare_with_reference(ref, self.spec["config"], params, samples, got,
                                     ref_len)
        W = self.cfg.swa_window
        out["wrapped_answers"] = sum(
            len(s["prompt"]) + min(len(s["answer"]), tr["check_decode_steps"]) > W
            for s in samples)
        out["answers_offered"] = len(payload["samples"])
        out["check_s"] = {"program_rows": t1 - t0, "reference": time.time() - t1}
        print(f"[check] {out['wrapped_answers']} of {len(samples)} compared answers "
              f"wrapped their ring; program rows {t1 - t0:.1f} s, reference "
              f"{time.time() - t1:.1f} s", flush=True)
        return out


def choose_samples(samples, window: int, decode_steps: int):
    """Of every answer the window served, two: the one whose context CROSSES
    the window soonest in its answer (prompt under the window, the crossing
    inside the `decode_steps` replayed positions: the ring wraps while it is
    decoded), and the one whose prompt is the shortest PAST the window (a
    walked pass; a row leaves the ring at its every step). Where the window
    served none of a kind: the answer of median prompt length in its place."""
    by_prompt = sorted(samples, key=lambda s: len(s["prompt"]))
    replayed = lambda s: min(len(s["answer"]), decode_steps)
    crossing = [s for s in by_prompt
                if len(s["prompt"]) <= window < len(s["prompt"]) + replayed(s) - 8]
    past = [s for s in by_prompt if len(s["prompt"]) > window]
    picked = crossing[-1:] + past[:1]
    median = by_prompt[len(by_prompt) // 2]
    if len(picked) < 2 and all(median is not s for s in picked):
        picked.append(median)
    return picked
