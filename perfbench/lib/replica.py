"""The benchmark's serve replica: builds config, weights (from the seed,
one jitted call) and the program's `ContinuousBatchingEngine` in the
replica's own process, warms the shapes the cell's traffic reaches, and puts
the benchmark's clocks and trace annotations around the engine's entry
points. The engine keeps no counters of its own, so the wrapping is done on
the INSTANCE (`engine.step`, `engine.submit`, `_collect_admissions`,
`_dispatch_prefill`, `_dispatch_decode`); no file of the program changes.
"""

from __future__ import annotations

import os
import shutil
import threading
import time


class BenchReplica:
    def __init__(self, spec: dict):
        t_enter = time.time()
        import jax
        import jax.numpy as jnp

        from perfbench.lib import model, worker
        from ray_tpu.models.serving import (ContinuousBatchingEngine,
                                            _write_slots, decode_step_fused,
                                            prefill_slots)

        self.spec = spec
        self.counter = worker.CompileCounter()
        self.spans = worker.Spans()
        self.fatal = None
        try:
            self.device = worker.device_report(1, spec["rehearsal"])
        except RuntimeError as e:
            # no chip: say so through `info` instead of dying in the
            # constructor, which Serve would answer by building another
            self.fatal = str(e)
            return
        t_device = time.time()
        run = spec["config"]["run"]
        self.cfg = cfg = model.model_config(spec["config"])
        self.params = model.make_params(cfg, spec["seed"])
        self.engine = eng = ContinuousBatchingEngine(
            self._served(self.params), cfg, num_slots=run["num_slots"],
            max_len=run["max_len"])
        self._lock = threading.Lock()
        self.requests = {}   # engine request id -> {submit, denied, admit}
        self.entries = {}    # client rid -> wall time stream() was entered
        self.steps = []      # (t_dispatch_decode, busy slots, live cache rows)
        self._wrap(eng)

        # warm exactly the programs the traffic reaches, on the engine's
        # own (donated) buffers, so that nothing compiles once load arrives
        warm = spec["traffic"]["warm"]
        n, z = eng.num_slots, jnp.zeros
        for nb in warm["admission_batches"]:
            # from Python lists, as `_dispatch_prefill` builds them: the
            # list -> int32 conversions are small programs of their own
            lens = jnp.asarray([1] * nb, jnp.int32)
            dropped = jnp.asarray([n] * nb, jnp.int32)  # out of range: no write
            for bucket in warm["prefill_buckets"]:
                first, k_rows, v_rows = prefill_slots(
                    eng.params, jnp.asarray([[0] * bucket] * nb, jnp.int32),
                    lens, cfg, eng.max_len)
                eng.k, eng.v, eng.lengths, eng.tokens = _write_slots(
                    eng.k, eng.v, eng.lengths, eng.tokens, dropped, k_rows,
                    v_rows, lens, first)
        for attn_len in warm["attention_buckets"]:
            eng.k, eng.v, eng.lengths, eng.tokens = decode_step_fused(
                eng.params, eng.k, eng.v, eng.lengths, eng.tokens, cfg, attn_len)
        eng.lengths = z((n,), jnp.int32)
        eng.tokens = z((n,), jnp.int32)
        jax.block_until_ready((eng.k, eng.v))
        self.times = {"t_enter": t_enter, "t_device": t_device,
                      "t_warm": time.time()}
        self.compile_setup = self.counter.snapshot()

    def _served(self, params):
        """The weights the engine serves: the seed's, or for a control run
        the same after a round trip through the lower precision. (The
        engine's own `quantize_weights=True` path cannot stand in: its Pallas
        quantiser runs out of VMEM on this model's stacked [49152, 8192]
        projections, PERF.md section 7.)"""
        how = self.spec.get("control")
        if not how:
            return params
        import jax

        from perfbench.lib.manifest import load_py

        ref = load_py(self.spec["reference_file"])
        return jax.jit(lambda p: ref.lower_precision(p, how))(params)

    def _wrap(self, eng) -> None:
        submit, collect = eng.submit, eng._collect_admissions
        prefill, decode, step = (eng._dispatch_prefill, eng._dispatch_decode,
                                 eng.step)

        def timed_submit(prompt, *, max_new_tokens=32):
            rid = submit(prompt, max_new_tokens=max_new_tokens)
            self.requests[rid] = {"submit": time.time()}
            return rid

        def timed_collect():
            admitted = collect()
            now = time.time()
            for _, reqs in admitted:
                for r in reqs:
                    self.requests.setdefault(r.request_id, {})["admit"] = now
            for r in eng._waiting:  # no slot was free for these
                self.requests.setdefault(r.request_id, {}).setdefault("denied", now)
            return admitted

        def timed_prefill(bucket, reqs):
            with self.spans.span("bench.prefill", bucket=bucket, n=len(reqs)):
                return prefill(bucket, reqs)

        def counted_decode():
            active = list(eng._active)
            self.steps.append((time.time(), len(active),
                               sum(eng._slot_pos[s] for s in active)))
            return decode()

        def timed_step():
            with self.spans.span("bench.engine_step"):
                return step()

        eng.submit, eng._collect_admissions = timed_submit, timed_collect
        eng._dispatch_prefill, eng._dispatch_decode = timed_prefill, counted_decode
        eng.step = timed_step

    def __serve_start__(self):
        if self.fatal is None:
            self.engine.start_driver()

    def __serve_stop__(self):
        if self.fatal is None and self.engine is not None:
            self.engine.stop_driver()

    # ----------------------------------------------------------- requests
    def stream(self, payload):
        self.entries[payload.get("rid")] = time.time()
        yield from self.engine.generate_stream(
            list(payload["prompt"]), max_new_tokens=int(payload["max_new_tokens"]))

    def info(self, _payload=None):
        if self.fatal is not None:
            return {"fatal": self.fatal}
        return {"device": self.device, "times": self.times,
                "compile_setup": self.compile_setup}

    # --------------------------------------------------------------- trace
    def trace_between(self, payload):
        """Trace from `start` to `stop` (wall clock) on a thread of the
        replica's own. Asked for ONCE, before the warm traffic, so that
        neither end of the trace queues behind the requests of a replica
        that is driven past what it carries (as two calls of their own the
        stop came 16 s late behind ~1,000 waiting requests: PERF.md section
        6, PR 52)."""
        self._trace_dir = os.path.join(self.spec["out_dir"], "trace")
        shutil.rmtree(self._trace_dir, ignore_errors=True)
        self._trace_times = {}

        def trace():
            import jax

            time.sleep(max(0.0, payload["start"] - time.time()))
            jax.profiler.start_trace(self._trace_dir)
            self._trace_times["started"] = time.time()
            time.sleep(max(0.0, payload["stop"] - time.time()))
            self._trace_times["stopped"] = time.time()
            jax.profiler.stop_trace()   # returns once the trace is written
            self._trace_times["written"] = time.time()

        self._tracer = threading.Thread(target=trace, daemon=True)
        self._tracer.start()
        return {}

    def trace_stop(self, _payload=None):
        """Called once the last answer is in: waits for the trace to be
        written and says when it began, ended and was written."""
        self._tracer.join(timeout=120.0)
        if "written" not in self._trace_times:
            raise RuntimeError(f"the trace did not end: {self._trace_times}")
        return self._trace_times

    def stats(self, payload=None):
        """Everything the benchmark's clocks and counters kept, and, after a
        traced run, the reduction of the trace."""
        from perfbench.lib import worker, xplane

        out = {
            "entries": {str(k): v for k, v in self.entries.items()},
            "requests": list(self.requests.values()),
            "steps": self.steps,
            "spans": {k: [(a, b, f) for a, b, f in v]
                      for k, v in self.spans.rows.items()},
            "lowering_times": list(self.counter.times),
            "memory_peak_bytes": worker.memory_peak_bytes(1),
        }
        if (payload or {}).get("trace"):
            out["trace"] = xplane.reduce_dir(self._trace_dir,
                                             self.spec["rehearsal"])
        return out

    # --------------------------------------------------------- correctness
    def check(self, payload):
        from perfbench.lib.manifest import load_py

        return compare_answers(
            load_py(self.spec["reference_file"]), self.spec["config"], self.cfg,
            self.params, self.engine.params, payload["samples"],
            self.engine.max_len)

    def control_sweep(self, payload):
        """For `perfbench/control.py`: over several seeds in this one
        process, the program's answers and the control's (the engine on
        weights rounded to int8) against the reference, at the cell's own size.
        The replica's serving engine is dropped first to make room."""
        from perfbench.lib import model, traffic
        from perfbench.lib.manifest import load_py
        from ray_tpu.models.serving import ContinuousBatchingEngine

        self.engine.stop_driver()
        run, tr = self.spec["config"]["run"], self.spec["traffic"]
        ref = load_py(self.spec["reference_file"])
        self.engine = self.params = None
        out = []
        for seed, mode in payload["runs"]:
            params = model.make_params(self.cfg, seed)
            self.spec["control"] = mode
            served = self._served(params)
            eng = ContinuousBatchingEngine(
                served, self.cfg, num_slots=run["num_slots"],
                max_len=run["max_len"])
            sched = traffic.open_loop(tr, seed, payload["seconds"],
                                      self.cfg.vocab_size)[:tr["check_answers"]]
            ids = [eng.submit(r["prompt"], max_new_tokens=r["max_new_tokens"])
                   for r in sched]
            eng.run_until_done()
            samples = [{"prompt": r["prompt"],
                        "answer": eng.result(i)[len(r["prompt"]):]}
                       for r, i in zip(sched, ids)]
            res = compare_answers(ref, self.spec["config"], self.cfg, params,
                                  served, samples, run["max_len"])
            res.pop("answers")
            out.append({"seed": seed, "mode": mode or "program", **res})
            del eng, params, served
        return out


def compare_answers(ref, c, cfg, params, served, samples, max_len) -> dict:
    """Served answers against the plain float32 reference, which is run
    teacher-forced over prompt + answer: for every token the engine chose,
    how far the reference's logit of it lies under the reference's top
    logit, in bf16 spacings of that logit (with random weights the top two
    are often a rounding apart, so tokens are not compared; a lower
    precision pushes the mean gap up); and the program's prefill logits
    (`prefill_kv` on the `served` weights: the function admission runs)
    against the reference's at two positions of every sample."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.serving import prefill_kv

    bucket = max_len - 1

    # fixed shapes inside two jitted programs: nothing here compiles anew
    # for another prompt or answer length
    @jax.jit
    def reference_rows(p, toks):
        want = ref.logits(p, toks, c)[0]                      # [max_len, V]
        nxt = jnp.roll(toks[0], -1)        # the token that followed each position
        top = jnp.max(want, axis=-1)
        got = jnp.take_along_axis(want, nxt[:, None], axis=-1)[:, 0]
        spacing = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(jnp.abs(top), 1e-30))) - 7)
        return want, (top - got) / spacing

    @jax.jit
    def row_rel_err(want, got_row, n):
        return ref.rel_err(got_row, jax.lax.dynamic_index_in_dim(
            want, n - 1, axis=0, keepdims=False))

    gaps, rel, per_answer = [], [], []
    for s in samples:
        prompt, answer = list(s["prompt"]), list(s["answer"])
        toks = np.zeros((1, max_len), np.int32)
        toks[0, :len(prompt) + len(answer)] = prompt + answer
        want, gap_rows = reference_rows(params, jnp.asarray(toks))
        g = np.asarray(gap_rows)[len(prompt) - 1: len(prompt) - 1 + len(answer)]
        gaps.extend(g.tolist())
        errs = []
        for i in (0, len(answer) // 2):
            n = len(prompt) + i
            row = np.zeros((1, bucket), np.int32)
            row[0, :n] = (prompt + answer)[:n]
            lg, _, _ = prefill_kv(served, jnp.asarray(row),
                                  jnp.asarray(n, jnp.int32), cfg, max_len)
            errs.append(float(row_rel_err(want, lg, jnp.asarray(n, jnp.int32))))
        rel.append(max(errs))
        per_answer.append({"prompt_len": len(prompt), "answer_len": len(answer),
                           "mean_gap_spacings": float(g.mean()),
                           "off_argmax": int((g > 0).sum()),
                           "prefill_logits_rel_err": max(errs)})
    return {"token_gap_mean_spacings": float(np.mean(gaps)),
            "prefill_logits_rel_err": max(rel), "answers": per_answer,
            "tokens_compared": len(gaps)}
