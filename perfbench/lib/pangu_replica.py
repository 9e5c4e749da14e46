"""The benchmark's serve replica for the openPangu-Ultra-MoE cut (rotary
latent attention, sandwich norms, held experts, a prediction module that
drafts): the record, clocks, trace annotations, wrapping of the engine's
entry points and warm-up through the engine's cache interface are
`lib.hybrid_replica.HybridBenchReplica`'s; what differs is how the model is
built (`lib.pangu_model`), that `check` drives the verify step (two
positions a slot) and compares the module's logits too, and that the
trace's reduction keeps the `mla_decode_attention` kernel's calls."""

from __future__ import annotations

import threading
import time

from perfbench.lib.hybrid_replica import HybridBenchReplica
from perfbench.lib.jamba_replica import _kernel_events

DECODE_KERNEL = "mla_decode_attention"
# the most positions of one sample the reference's rows are compared at (two
# of the prompt, then its last and `check_decode_steps` behind it): one shape
COMPARED_ROWS = 16


class PanguBenchReplica(HybridBenchReplica):
    def __init__(self, spec: dict):
        t_enter = time.time()
        import jax
        import jax.numpy as jnp

        from perfbench.lib import pangu_model, worker
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
        self.cfg = cfg = pangu_model.model_config(spec["config"])
        self.params = pangu_model.make_params(cfg, spec["seed"])
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
        holds, under `kernel_calls`, the `mla_decode_attention` kernel's
        [events, seconds]: one event an MLA layer and decode step. Read
        before the parent's reduction, which removes the trace."""
        from perfbench.lib import xplane

        calls = {}
        if (payload or {}).get("trace"):
            try:
                events = _kernel_events(
                    xplane.load(xplane.find_xplane(self._trace_dir)), DECODE_KERNEL)
                calls = {DECODE_KERNEL: [len(events), sum(t for _, t in events)]}
            except ValueError:
                if not self.spec["rehearsal"]:
                    raise
        out = super().stats(payload)
        if out.get("trace"):
            out["trace"]["kernel_calls"] = calls
        return out

    # --------------------------------------------------------- correctness
    def check(self, payload):
        import gc

        from perfbench.lib import pangu_model
        from perfbench.lib.manifest import load_py

        ref = load_py(self.spec["reference_file"])
        tr = self.spec["traffic"]
        got = program_rows(self.engine, payload["samples"],
                           tr["prompt_tokens"]["max"], tr["check_decode_steps"])
        # the reference needs the room the engine's slot state takes
        self.engine.stop_driver()
        self.engine.cache.state = None
        self.engine = None
        gc.collect()         # the instance's wrapped methods point back at it
        params = self.params
        if params is None:   # a control run: remake the seed's weights
            params = pangu_model.make_params(self.cfg, self.spec["seed"])
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
    (`hybrid.prefill(with_routing=True, first=...)`: admission's program with
    the logits, the module's logits and every position's choice of experts
    returned as well, the module at the last position fed the token the
    answer began with: what the engine fed it in the window). The state
    rows of the whole prompts, the module's and its draft with them, go
    into slots of the engine's cache, spread over it, by the engine's own
    `cache.write`.

    Decode: the samples, all live at once among the engine's idle slots,
    go through the engine's VERIFY step, teacher-forced, by
    `hybrid.verify_logits`: the step program's body over the engine's
    donated state with only their slots active, two positions a slot, the
    slot then advanced by 2 (a draft that held) and by 1 (a refused one,
    whose row stays behind and is overwritten) in turn, from the position
    behind the prompt until `decode_steps` positions have been kept.

    -> per sample {"rows": {position: the prompt pass's logits},
    "decode_rows": {position: the verify step's}, "mtp_rows": {position:
    the module's logits: the prompt pass's at the prompt's last position, the
    verify step's behind it}, "routing": [expert layers +
    1, positions, k]}."""
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
    out = [{"rows": {}, "decode_rows": {}, "mtp_rows": {}, "routing": {}}
           for _ in samples]
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
                    lens, slots, first = [1] * nb, [B] * nb, [0] * nb  # B: dropped
                    for j, (i, upto) in enumerate(group):
                        seq = samples[i]["prompt"] + samples[i]["answer"]
                        toks[j, :upto] = seq[:upto]
                        # teacher-forced: the module at the last position is
                        # fed the token that FOLLOWED in the window, as the
                        # engine fed it and as the reference will, not the
                        # one this pass's shape would choose at a near-tie
                        lens[j], first[j] = upto, seq[upto]
                        if upto == len(samples[i]["prompt"]):
                            slots[j] = slot[i]
                    lens, first = (jnp.asarray(a, jnp.int32) for a in (lens, first))
                    logits, rows = hybrid.prefill(served, jnp.asarray(toks), lens,
                                                  cfg, with_routing=True, first=first)
                    routing = np.asarray(rows.pop("routing"))
                    module = np.asarray(rows.pop("mtp_logits"))
                    for j, (i, upto) in enumerate(group):
                        out[i]["rows"][upto - 1] = np.asarray(logits[j])
                        if slots[j] < B:
                            out[i]["mtp_rows"][upto - 1] = module[j]
                            for pos in range(upto):
                                out[i]["routing"][pos] = routing[:, j, pos]
                    engine.lengths, engine.tokens = cache.write(
                        engine.lengths, engine.tokens, jnp.asarray(slots, jnp.int32),
                        rows, lens, first)
            active = np.zeros((B,), bool)
            active[[slot[i] for i in wave]] = True
            attn_len = _attn_bucket(
                max(len(samples[i]["prompt"]) for i in wave) + decode_steps + 1,
                max_len)
            kept, turn = 0, 0
            while kept < decode_steps:
                keep = min(2 - turn % 2, decode_steps - kept)
                toks = np.zeros((B, 2), np.int32)
                nxt = np.zeros((B, 2), np.int32)
                for i in wave:
                    seq = (samples[i]["answer"][kept:kept + 3] + [0, 0, 0])[:3]
                    toks[slot[i]], nxt[slot[i]] = seq[:2], seq[1:]
                cache.state, logits, module, chose = hybrid.verify_logits(
                    served, cache.state, engine.lengths, jnp.asarray(toks),
                    jnp.asarray(nxt), jnp.asarray(active), cfg, attn_len)
                logits, module, chose = (np.asarray(a) for a in (logits, module, chose))
                for i in wave:
                    n = len(samples[i]["prompt"])
                    for a in range(2):
                        pos = n + kept + a
                        if kept + a + 1 < len(samples[i]["answer"]):
                            out[i]["decode_rows"][pos] = logits[slot[i], a]
                            out[i]["mtp_rows"][pos] = module[slot[i], a]
                            out[i]["routing"][pos] = chose[:, slot[i], a]
                engine.lengths = engine.lengths + keep * jnp.asarray(active, jnp.int32)
                kept, turn = kept + keep, turn + 1
        engine.lengths = jnp.zeros((B,), jnp.int32)
        engine.tokens = jnp.zeros((B,), jnp.int32)
    for o in out:
        o["routing"] = np.stack([o["routing"][p] for p in range(len(o["routing"]))],
                                axis=1)
    return out


def compare_with_reference(ref, c, params, samples, got, ref_len) -> dict:
    """The plain float32 reference against what was served, four numbers
    (`lib.hybrid_replica.compare_with_reference` says why the first three
    are what they are).

    `token_gap_mean_spacings`: the reference teacher-forced over prompt +
    answer (ONE fixed shape, `ref_len` positions; its own routing): for
    every token the engine chose, how far the reference's logit of it lies
    under the reference's top logit, in bf16 spacings of that logit.

    `prefill_logits_rel_err`: the relative error of the program's main
    logits (`program_rows`: prefill, then the verify step through the slot
    state) against the reference's at the same positions, the reference
    following the PROGRAM's choice of experts.

    `mtp_logits_rel_err`: the same for the prediction module's logits, at
    the prompt's last position (the prompt pass's own, the row that makes a
    request's first draft) and at every verified position behind it, which
    attend to the rows the prompt pass left the module.

    `route_margin_max`: how far, at worst, an expert the program chose
    scores (score + bias, in the reference's own arithmetic) under the
    reference's 8th best, the module's layer included."""
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
    def reference_routed(p, toks, routing, at):
        # only the rows that are compared leave the program: two arrays of
        # [ref_len, vocab] float32 a sample are 1.2 GB beside the weights
        main, module, worst = ref.logits_routed(p, toks, c, routing)
        return main[0][at], module[0][at], worst

    layers, _, k = got[0]["routing"].shape
    gaps, rel, rel_mtp, margins, per_answer = [], [], [], [], []
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
        at = sorted(set(g["rows"]) | set(g["decode_rows"]) | set(g["mtp_rows"]))
        where = {pos: j for j, pos in enumerate(at)}
        main, module, worst = reference_routed(
            params, jnp.asarray(toks), jnp.asarray(routing),
            jnp.asarray(at + [0] * (COMPARED_ROWS - len(at)), jnp.int32))
        main, module = np.asarray(main), np.asarray(module)
        prefill, decode = (max(float(ref.rel_err(row, main[where[pos]]))
                               for pos, row in g[key].items())
                           for key in ("rows", "decode_rows"))
        errs_mtp = [float(ref.rel_err(row, module[where[pos]]))
                    for pos, row in g["mtp_rows"].items()]
        rel.append(max(prefill, decode))
        rel_mtp.append(max(errs_mtp))
        margins.append(float(worst))
        per_answer.append({"prompt_len": len(prompt), "answer_len": len(answer),
                           "mean_gap_spacings": float(gap.mean()),
                           "off_argmax": int((gap > 0).sum()),
                           "prefill_logits_rel_err": prefill,
                           "decode_logits_rel_err": decode,
                           "mtp_logits_rel_err": max(errs_mtp),
                           "mtp_rows_compared": len(errs_mtp),
                           "route_margin": float(worst)})
    return {"token_gap_mean_spacings": float(np.mean(gaps)),
            "prefill_logits_rel_err": max(rel), "mtp_logits_rel_err": max(rel_mtp),
            "route_margin_max": max(margins),
            "answers": per_answer, "tokens_compared": len(gaps)}
