"""The benchmark's serve replica for the hybrid (Kimi-Linear) model: the
same record, clocks, trace annotations and wrapping of the engine's entry
points as `lib.replica.BenchReplica`; what differs is how the model is
built (`lib.hybrid_model`), how the engine's programs are warmed (through
the engine's cache interface) and what `check` compares."""

from __future__ import annotations

import threading
import time

from perfbench.lib.replica import BenchReplica


class HybridBenchReplica(BenchReplica):
    def __init__(self, spec: dict):
        t_enter = time.time()
        import jax
        import jax.numpy as jnp

        from perfbench.lib import hybrid_model, worker
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
        self.cfg = cfg = hybrid_model.model_config(spec["config"])
        self.params = hybrid_model.make_params(cfg, spec["seed"])
        served = self._served(self.params)
        if spec.get("control"):
            # two copies of these weights do not fit one chip: the control
            # keeps the rounded ones and `check` makes the seed's again
            self.params = None
        self.engine = eng = ContinuousBatchingEngine(
            served, cfg, num_slots=run["num_slots"], max_len=run["max_len"])
        self._lock = threading.Lock()
        self.requests, self.entries, self.steps = {}, {}, []
        self._wrap(eng)

        # warm exactly the programs the traffic reaches, through the
        # engine's own cache (donated buffers), as `_dispatch_prefill` and
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

    def _served(self, params):
        how = self.spec.get("control")
        if not how:
            return params
        import jax

        from perfbench.lib.manifest import load_py

        ref = load_py(self.spec["reference_file"])
        # donated: the rounded weights take the place of the seed's
        return jax.jit(lambda p: ref.lower_precision(p, how),
                       donate_argnums=0)(params)

    def control_sweep(self, payload):
        raise NotImplementedError(
            "run the control as `run.py --control int8` on this cell: two "
            "sets of these weights do not fit the chip beside each other")

    def stats(self, payload=None):
        """The record of `BenchReplica.stats`; the trace's reduction keeps
        the forty operations that took most device time (`device_ops_top40`,
        for `last_run.json`) beside the ten of the result line: nine
        unrolled layers of four kinds spread a step over many operations."""
        import shutil

        from perfbench.lib import xplane

        out = super().stats({"trace": False})
        if (payload or {}).get("trace"):
            try:
                red = xplane.reduce(xplane.load(xplane.find_xplane(self._trace_dir)),
                                    top=40)
                red["device_ops_top40"] = red["device_ops"]
                red["device_ops"] = red["device_ops"][:10]
                red["idle_gaps"] = red["idle_gaps"][:10]
                out["trace"] = red
            except ValueError:
                if not self.spec["rehearsal"]:
                    raise
                out["trace"] = None   # a CPU rehearsal has no device plane
            finally:
                shutil.rmtree(self._trace_dir, ignore_errors=True)
        return out

    # --------------------------------------------------------- correctness
    def check(self, payload):
        import gc

        from perfbench.lib import hybrid_model
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
            params = hybrid_model.make_params(self.cfg, self.spec["seed"])
        return compare_with_reference(
            ref, self.spec["config"], params, payload["samples"], got,
            tr["prompt_tokens"]["max"] + tr["answer_tokens"]["max"])


def program_rows(engine, samples, longest_prompt, decode_steps):
    """What the ENGINE that served the window computes for each sample, as
    numpy, through its own slot state after the window (nothing is live any
    more; the stepper is held off).

    Prefill: every sample's whole prompt and its first half are admitted the
    way `_dispatch_prefill` admits, in two of the (batch, bucket) shapes the
    window used: four requests to a call at the bucket a call of four may
    have, one to a call at the bucket of the longest prompt
    (`hybrid.prefill(with_routing=True)`: admission's program with the logits
    and every position's choice of experts returned as well). The state rows
    of the whole prompts go into slots of the engine's cache, spread over it,
    by the engine's own `cache.write`.

    Decode: the samples, all live at once among the engine's idle slots,
    are decoded `decode_steps` tokens, teacher-forced, by
    `hybrid.decode_logits`: the step program's body over the engine's donated
    state with only their slots active, as `_dispatch_decode` runs it.

    -> per sample {"rows": {position: logits}, "routing": [expert layers,
    prompt + decode_steps, k]}."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import hybrid
    from ray_tpu.models.serving import _attn_bucket, _bucket_len

    cfg, served, cache = engine.cfg, engine.params, engine.cache
    B, max_len = engine.num_slots, engine.max_len
    big = _bucket_len(longest_prompt, max_len)
    shapes = [(cache.max_prefill_batch(b) or 4, b)
              for b in sorted({min(big, max(8, cfg.prefill_tokens // 4)), big})]
    spread = [(7 * j + 3) % B for j in range(B)] if B % 7 else list(range(B))
    out = [{"rows": {}, "routing": []} for _ in samples]
    with engine._step_lock:
        for w0 in range(0, len(samples), B):      # as many at once as slots
            wave = range(w0, min(w0 + B, len(samples)))
            slot = {i: spread[i - w0] for i in wave}
            calls = {shape: [] for shape in shapes}
            for i in wave:
                n = len(samples[i]["prompt"])
                # the half rides in the shape of the whole: the reference
                # follows the routing of the whole prompt's pass, and only
                # the same program turns every near-tie the same way
                shape = next(s for s in shapes if s[1] >= n)
                for upto in sorted({n, max(1, n // 2)}, reverse=True):
                    calls[shape].append((i, upto))
            for (most, bucket), reqs in calls.items():
                for at in range(0, len(reqs), most):
                    group = reqs[at:at + most]
                    nb = most             # one program a shape
                    toks = np.zeros((nb, bucket), np.int32)
                    lens, slots = [1] * nb, [B] * nb          # B: dropped
                    for j, (i, upto) in enumerate(group):
                        toks[j, :upto] = samples[i]["prompt"][:upto]
                        lens[j] = upto
                        if upto == len(samples[i]["prompt"]):
                            slots[j] = slot[i]
                    lens = jnp.asarray(lens, jnp.int32)
                    logits, rows = hybrid.prefill(served, jnp.asarray(toks), lens,
                                                  cfg, with_routing=True)
                    routing = np.asarray(rows.pop("routing"))
                    for j, (i, upto) in enumerate(group):
                        out[i]["rows"][upto - 1] = np.asarray(logits[j])
                        if slots[j] < B:
                            out[i]["routing"].append(routing[:, j, :upto])
                    engine.lengths, engine.tokens = cache.write(
                        engine.lengths, engine.tokens, jnp.asarray(slots, jnp.int32),
                        rows, lens, jnp.zeros((nb,), jnp.int32))
            active = np.zeros((B,), bool)
            active[[slot[i] for i in wave]] = True
            attn_len = _attn_bucket(
                max(len(samples[i]["prompt"]) for i in wave) + decode_steps, max_len)
            for t in range(decode_steps):
                toks = np.zeros((B,), np.int32)
                for i in wave:
                    toks[slot[i]] = (samples[i]["answer"][t:t + 1] or [0])[0]
                cache.state, logits, chose = hybrid.decode_logits(
                    served, cache.state, engine.lengths, jnp.asarray(toks),
                    jnp.asarray(active), cfg, attn_len)
                engine.lengths = engine.lengths + 1
                logits, chose = np.asarray(logits), np.asarray(chose)
                for i in wave:
                    if t < len(samples[i]["answer"]):
                        n = len(samples[i]["prompt"])
                        out[i]["rows"][n + t] = logits[slot[i]]
                        out[i]["routing"].append(chose[:, slot[i]][:, None])
        engine.lengths = jnp.zeros((B,), jnp.int32)
        engine.tokens = jnp.zeros((B,), jnp.int32)
    for o in out:
        o["routing"] = np.concatenate(o["routing"], axis=1)
    return out


def compare_with_reference(ref, c, params, samples, got, ref_len) -> dict:
    """The plain float32 reference against what was served, three numbers.

    `token_gap_mean_spacings`: the reference teacher-forced over prompt +
    answer (ONE fixed shape, `ref_len` positions; its own routing): for
    every token the engine chose, how far the reference's logit of it lies
    under the reference's top logit, in bf16 spacings of that logit (with
    random weights the top two are often a rounding apart, so tokens are not
    compared; a lower precision pushes the mean gap up).

    `prefill_logits_rel_err`: the relative error of the program's logits
    (`program_rows`: prefill, then decode through the slot state) against
    the reference's at the same positions, the reference following the
    PROGRAM's choice of experts (`logits_routed`): 8 of 256 experts by
    score leave the 8th and 9th a few thousandths apart, bf16 rounding
    turns that choice for a token in ten, and each turn moves the token's
    hidden state by a tenth, which is no error of arithmetic.

    `route_margin_max`: what keeps that honest: how far, at worst, an
    expert the program chose scores (score + bias, in the reference's own
    arithmetic) under the reference's 8th best. A near-tie is thousandths."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    @jax.jit
    def reference_gaps(p, toks):
        want = ref.logits(p, toks, c)[0]                      # [ref_len, V]
        nxt = jnp.roll(toks[0], -1)        # the token that followed each position
        top = jnp.max(want, axis=-1)
        chosen = jnp.take_along_axis(want, nxt[:, None], axis=-1)[:, 0]
        spacing = 2.0 ** (jnp.floor(jnp.log2(jnp.maximum(jnp.abs(top), 1e-30))) - 7)
        return (top - chosen) / spacing

    @jax.jit
    def reference_routed(p, toks, routing):
        want, worst = ref.logits_routed(p, toks, c, routing)
        return want[0], worst

    layers, _, k = got[0]["routing"].shape
    gaps, rel, margins, per_answer = [], [], [], []
    for s, g in zip(samples, got):
        prompt, answer = list(s["prompt"]), list(s["answer"])
        toks = np.zeros((1, ref_len), np.int32)
        toks[0, :len(prompt) + len(answer)] = prompt + answer
        gap = np.asarray(reference_gaps(params, jnp.asarray(toks)))
        gap = gap[len(prompt) - 1: len(prompt) - 1 + len(answer)]
        gaps.extend(gap.tolist())
        n = g["routing"].shape[1]
        routing = np.full((layers, 1, ref_len, k), -1, np.int32)  # -1: free
        routing[:, 0, :n] = g["routing"]
        want, worst = reference_routed(params, jnp.asarray(toks), jnp.asarray(routing))
        errs = {pos: float(ref.rel_err(jnp.asarray(row), want[pos]))
                for pos, row in g["rows"].items()}
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
