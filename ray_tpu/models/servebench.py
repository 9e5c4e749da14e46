"""SERVEBENCH: serving-engine performance artifact (decode fast lanes).

Measures what the continuous-batching engine actually delivers, separated
the way capacity planning needs it:

  * ``decode_tokens_per_s`` / per chip — steady-state fused-decode
    throughput with every slot busy (the flagship row; bounds rollout
    tokens/s for a serve+train fleet);
  * a slot sweep (1/4/8) — how throughput scales with continuous-batching
    occupancy;
  * bf16 vs w8a16 — the quantized engine on the SAME fast loop, with a
    logits-parity check so the quantized row is honest, and the measured
    weight-bytes ratio to validate (or retract) the "weight traffic
    halves" claim on this backend;
  * ``prefill_tokens_per_s`` — batched bucketed admission throughput,
    reported separately from decode (they bound different phases);
  * p50/p99 request latency under the storm harness's open-loop load
    generator driving a real Serve deployment of `LLMDeployment`.

Run:

    python -m ray_tpu.models.servebench                # quick profile
    python -m ray_tpu.models.servebench --json SERVEBENCH_r16.json \
        --baseline /tmp/servebench_baseline.json       # embed pre-change run

Artifact-regeneration policy: the committed SERVEBENCH_r{N}.json is a
full quick-profile run on the committing box; CI re-runs the same profile
and fails on missing rows, while `tests/test_envelope.py` pins machine-
calibrated floors on the decode/prefill rows (0.5x-slack discipline).
"""

from __future__ import annotations

import json
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

DEFAULT_ARTIFACT = "SERVEBENCH_r16.json"

# Quick-profile model: small enough to compile/run on a 1-CPU CI box in
# seconds, big enough (GQA 8/4 heads, 4 layers) that the decode loop has
# the same shape as the flagship configs. dtype stays f32 on CPU — the
# "bf16" label tracks the flagship intent; the artifact records the real
# dtype of the run.
_QUICK = dict(vocab_size=2048, d_model=256, n_layers=4, n_heads=8,
              n_kv_heads=4, d_ff=1024, max_seq_len=512)
_QUICK_MAX_LEN = 512
_PROMPT = [1, 2, 3, 4, 5, 6, 7]


def _bench_model(quick: bool = True):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import ModelConfig, init_params

    if quick:
        cfg = ModelConfig(dtype=jnp.float32, remat="none", **_QUICK)
        max_len = _QUICK_MAX_LEN
    else:
        cfg = ModelConfig.b1()
        max_len = 2048
    params = init_params(jax.random.PRNGKey(0), cfg)
    return params, cfg, max_len


def measure_decode(params, cfg, *, num_slots: int, max_len: int,
                   steps: int = 40, warm_steps: int = 10,
                   quantize_weights: bool = False) -> Dict[str, float]:
    """Steady-state decode throughput with every slot occupied. The warmup
    compiles the admission + decode kernels and the measured window stays
    inside one attention bucket, so the number is pure decode-loop speed
    (bucket recompiles are a once-per-depth cost, not a per-token one)."""
    from ray_tpu.models.serving import ContinuousBatchingEngine

    eng = ContinuousBatchingEngine(params, cfg, num_slots=num_slots,
                                   max_len=max_len,
                                   quantize_weights=quantize_weights)
    for i in range(num_slots):
        eng.submit([t + i for t in _PROMPT], max_new_tokens=10 ** 6)
    for _ in range(warm_steps):
        eng.step()
    t0 = time.perf_counter()
    for _ in range(steps):
        eng.step()
    dt = time.perf_counter() - t0
    steps_per_s = steps / dt
    return {
        "num_slots": num_slots,
        "steps_per_s": round(steps_per_s, 2),
        "decode_tokens_per_s": round(steps_per_s * num_slots, 2),
        "ms_per_step": round(1e3 * dt / steps, 3),
    }


def measure_prefill(params, cfg, *, max_len: int, bucket: int = 64,
                    batch: int = 4, iters: int = 8) -> Dict[str, float]:
    """Batched bucketed admission throughput: one `prefill_slots` call per
    iteration over `batch` right-padded prompts of `bucket` tokens."""
    import jax.numpy as jnp

    from ray_tpu.models.serving import prefill_slots

    tokens = jnp.tile(jnp.arange(1, bucket + 1, dtype=jnp.int32)[None],
                      (batch, 1))
    true_len = jnp.full((batch,), bucket, jnp.int32)
    first, k, v = prefill_slots(params, tokens, true_len, cfg, max_len)
    np.asarray(first)  # compile + settle
    t0 = time.perf_counter()
    for _ in range(iters):
        first, k, v = prefill_slots(params, tokens, true_len, cfg, max_len)
    np.asarray(first)
    dt = time.perf_counter() - t0
    return {
        "batch": batch,
        "prompt_len": bucket,
        "prefill_tokens_per_s": round(iters * batch * bucket / dt, 1),
        "ms_per_call": round(1e3 * dt / iters, 3),
    }


def measure_quant_parity(params, cfg, *, max_len: int) -> Dict[str, Any]:
    """Honesty check for the w8a16 row: logits max-abs-diff (relative to
    the unquantized logit scale) on a probe prompt, plus the measured
    weight-bytes ratio (the "weight traffic halves" claim is about bytes
    read per decode step — on an HBM-bound TPU decode that ratio IS the
    speedup bound; on a compute-bound CPU it is not)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.inference import prefill
    from ray_tpu.models.serving import quantize_model_params

    qparams = quantize_model_params(params, cfg)

    def nbytes(tree):
        return sum(x.size * x.dtype.itemsize
                   for x in jax.tree_util.tree_leaves(tree))

    tokens = jnp.asarray([_PROMPT + [9, 22, 7]], jnp.int32)
    ref, _ = prefill(params, tokens, cfg, max_len)
    qlog, _ = prefill(qparams, tokens, cfg, max_len)
    ref = np.asarray(ref, np.float32)
    qlog = np.asarray(qlog, np.float32)
    rel = float(np.abs(ref - qlog).max() / (np.abs(ref).max() + 1e-6))
    return {
        "logits_max_abs_diff_rel": round(rel, 5),
        "logits_parity_ok": rel < 0.08,
        "weight_bytes_ratio": round(nbytes(qparams) / nbytes(params), 4),
    }


def measure_latency_under_load(params, cfg, *, max_len: int,
                               num_slots: int = 8, duration_s: float = 5.0,
                               rps: float = 6.0, max_new_tokens: int = 16,
                               request_timeout_s: float = 20.0
                               ) -> Dict[str, Any]:
    """p50/p99 request latency for a REAL Serve deployment of
    `LLMDeployment` (replica engine in driver mode via the
    `__serve_start__` hook) under the storm harness's open-loop load
    generator. Needs an initialized ray_tpu runtime."""
    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.models.serving import LLMDeployment
    from ray_tpu.serve.storm import LoadGenerator
    from ray_tpu.util.stats import percentile

    D = serve.deployment(name="servebench_llm", num_replicas=1,
                         max_concurrent_queries=num_slots)(
        LLMDeployment(params, cfg, num_slots=num_slots, max_len=max_len))
    handle = serve.run(D.bind(), name="servebench")
    try:
        # warm: compile prefill/admission/decode variants before the clock
        for wave in (num_slots, num_slots // 2 or 1, 2, 1):
            ray_tpu.get([handle.remote({"prompt": _PROMPT,
                                        "max_new_tokens": max_new_tokens})
                         for _ in range(wave)], timeout=120)
        gen = LoadGenerator(
            handle, rps=rps, request_timeout_s=request_timeout_s,
            payload_fn=lambda idx, i: {"prompt": _PROMPT,
                                       "max_new_tokens": max_new_tokens},
            threads=2)
        out = gen.run(duration_s)
        lat = sorted(out.latencies_ms)
        return {
            "offered_rps": rps,
            "duration_s": round(gen.elapsed_s, 2),
            "max_new_tokens": max_new_tokens,
            "submitted": out.submitted,
            "accepted": out.accepted,
            "shed": out.shed,
            "timeout": out.timeout,
            "errors": out.replica_death + out.other_error,
            "hung": out.hung,
            "p50_ms": round(percentile(lat, 0.50) or 0.0, 2),
            "p99_ms": round(percentile(lat, 0.99) or 0.0, 2),
        }
    finally:
        serve.delete("servebench_llm")


def run_servebench(quick: bool = True, *,
                   slot_sweep: Sequence[int] = (1, 4, 8),
                   with_latency: bool = True,
                   baseline: Optional[Dict[str, Any]] = None
                   ) -> Dict[str, Any]:
    import jax

    backend = jax.default_backend()
    if with_latency and backend != "cpu":
        # One process per chip: the engine rows below make THIS process the
        # chip's owner, and the deployment round's replica would then run
        # on a CPU worker (or hang on the chip) under the same "backend".
        raise SystemExit(
            f"servebench on backend={backend!r}: the in-process engine rows "
            "and the Serve deployment round cannot share one run — this "
            "process would hold the chip the replica needs. Run the engine "
            "rows with --no-latency; drive a chip-owning replica through "
            "Serve with chip_smoke.py (ray_tpu.serve.llm.LLMReplica, "
            "resources={'TPU': 1}).")
    params, cfg, max_len = _bench_model(quick)
    devices = jax.devices()
    n_chips = len(devices)

    sweep = [measure_decode(params, cfg, num_slots=s, max_len=max_len)
             for s in slot_sweep]
    flagship = sweep[-1]
    quant = measure_quant_parity(params, cfg, max_len=max_len)
    quant_decode = measure_decode(params, cfg, num_slots=slot_sweep[-1],
                                  max_len=max_len, quantize_weights=True)
    speed_ratio = (quant_decode["decode_tokens_per_s"]
                   / max(flagship["decode_tokens_per_s"], 1e-9))
    # The claim: int8 weights halve weight traffic, so HBM-bound decode
    # speeds up ~2x. Validated only where decode IS weight-traffic-bound;
    # a compute-bound backend (CPU) pays dequant FLOPs instead. Record the
    # verdict for THIS backend rather than asserting the TPU story.
    quant_row = {
        **quant,
        "decode_tokens_per_s": quant_decode["decode_tokens_per_s"],
        "speedup_vs_unquantized": round(speed_ratio, 3),
        "weight_traffic_halves_claim": {
            "weight_bytes_ratio": quant["weight_bytes_ratio"],
            "bytes_claim_validated": quant["weight_bytes_ratio"] <= 0.55,
            "throughput_claim_validated_on_this_backend":
                speed_ratio >= 1.5,
            "backend": backend,
            "note": ("weight bytes shrink as claimed; the 2x decode "
                     "speedup only follows where decode is weight-"
                     "traffic-bound (TPU HBM), not on a compute-bound "
                     f"backend like {backend}" if speed_ratio < 1.5 else
                     "validated end to end on this backend"),
        },
    }
    prefill_row = measure_prefill(params, cfg, max_len=max_len)

    art: Dict[str, Any] = {
        "bench": "servebench",
        "round": 16,
        "profile": "quick" if quick else "full",
        "backend": backend,
        "n_chips": n_chips,
        "model": {
            "d_model": cfg.d_model, "n_layers": cfg.n_layers,
            "n_heads": cfg.n_heads, "n_kv_heads": cfg.n_kv_heads,
            "d_ff": cfg.d_ff, "vocab_size": cfg.vocab_size,
            "dtype": str(cfg.dtype.__name__
                         if hasattr(cfg.dtype, "__name__") else cfg.dtype),
            "max_len": max_len,
        },
        "decode": {
            "decode_tokens_per_s": flagship["decode_tokens_per_s"],
            "decode_tokens_per_s_per_chip": round(
                flagship["decode_tokens_per_s"] / n_chips, 2),
            "steps_per_s": flagship["steps_per_s"],
            "ms_per_step": flagship["ms_per_step"],
            "num_slots": flagship["num_slots"],
        },
        "slot_sweep": sweep,
        "w8a16": quant_row,
        "prefill": prefill_row,
    }
    if baseline is not None:
        art["baseline_pre_change"] = baseline
        base = baseline.get("slot_sweep", baseline)
        key = str(flagship["num_slots"])
        base_row = base.get(key) if isinstance(base, dict) else None
        if base_row and base_row.get("decode_tokens_per_s"):
            art["decode"]["speedup_vs_baseline"] = round(
                flagship["decode_tokens_per_s"]
                / base_row["decode_tokens_per_s"], 2)
    if with_latency:
        import ray_tpu

        owns_runtime = not ray_tpu.is_initialized()
        if owns_runtime:
            ray_tpu.init(num_cpus=8, resources={"TPU": 8})
        try:
            art["latency_under_load"] = measure_latency_under_load(
                params, cfg, max_len=max_len)
        finally:
            if owns_runtime:
                try:
                    from ray_tpu import serve

                    serve.shutdown()
                finally:
                    ray_tpu.shutdown()
    return art


REQUIRED_ROWS = ("decode", "slot_sweep", "w8a16", "prefill",
                 "latency_under_load")


def main(argv: Optional[List[str]] = None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--json", default=DEFAULT_ARTIFACT,
                    help=f"artifact path (default {DEFAULT_ARTIFACT}; "
                         f"'' to skip writing)")
    ap.add_argument("--full", action="store_true",
                    help="flagship-config profile (TPU-sized; default is "
                         "the quick CI profile)")
    ap.add_argument("--no-latency", action="store_true",
                    help="skip the serve-deployment latency rows (no "
                         "runtime spin-up)")
    ap.add_argument("--baseline", default=None,
                    help="JSON file with pre-change decode numbers to "
                         "embed as baseline_pre_change")
    args = ap.parse_args(argv)

    baseline = None
    if args.baseline:
        with open(args.baseline) as f:
            baseline = json.load(f)
    art = run_servebench(quick=not args.full,
                         with_latency=not args.no_latency,
                         baseline=baseline)

    dec = art["decode"]
    print(f"servebench [{art['profile']}] backend={art['backend']} "
          f"chips={art['n_chips']}")
    print(f"  decode: {dec['decode_tokens_per_s']} tok/s "
          f"({dec['decode_tokens_per_s_per_chip']} tok/s/chip, "
          f"{dec['ms_per_step']} ms/step @ {dec['num_slots']} slots"
          + (f", {dec['speedup_vs_baseline']}x vs pre-change baseline"
             if "speedup_vs_baseline" in dec else "") + ")")
    print("  slots  steps/s  tok/s")
    for row in art["slot_sweep"]:
        print(f"  {row['num_slots']:>5}  {row['steps_per_s']:>7} "
              f"{row['decode_tokens_per_s']:>6}")
    q = art["w8a16"]
    print(f"  w8a16: {q['decode_tokens_per_s']} tok/s "
          f"({q['speedup_vs_unquantized']}x vs unquantized), "
          f"weight bytes {q['weight_bytes_ratio']}x, "
          f"logits rel err {q['logits_max_abs_diff_rel']}")
    print(f"  prefill: {art['prefill']['prefill_tokens_per_s']} tok/s "
          f"(batch {art['prefill']['batch']} x "
          f"{art['prefill']['prompt_len']} tokens)")
    if "latency_under_load" in art:
        lat = art["latency_under_load"]
        print(f"  latency under load: p50 {lat['p50_ms']}ms "
              f"p99 {lat['p99_ms']}ms ({lat['accepted']}/{lat['submitted']} "
              f"accepted @ {lat['offered_rps']} rps, hung={lat['hung']})")

    missing = [r for r in REQUIRED_ROWS
               if r not in art and not (r == "latency_under_load"
                                        and args.no_latency)]
    if missing:
        print(f"SERVEBENCH FAILED: missing rows {missing}")
        return 1
    if args.json:
        with open(args.json, "w") as f:
            json.dump(art, f, indent=2, sort_keys=True)
        print(f"  artifact: {args.json}")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
