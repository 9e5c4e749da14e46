"""Driver of `open_loop` traffic: HTTP streaming route -> proxy -> router ->
one replica -> `ContinuousBatchingEngine`, requests sent when they are due.

The parent (never on jax) deploys `lib.replica.BenchReplica` through
`serve.run` with a `TPU: 1` grant, starts the HTTP proxy, and is the load
generator: one asyncio loop, one connection per request. `warm_s` seconds of
the same traffic run before the window opens (they count as set-up), so that
it opens at steady occupancy; requests still running when it closes are
drained and counted. Afterwards a seeded sample of answers goes back to the
replica to be compared with the float32 reference.
"""

from __future__ import annotations

import asyncio
import random
import time

from perfbench.lib import loadgen, manifest, traffic as traffic_mod


def run(ctx) -> dict:
    import ray_tpu
    from perfbench.lib.replica import BenchReplica
    from ray_tpu import serve

    cell, tr, config = ctx["cell"], ctx["traffic"], ctx["config"]
    run_cfg, seconds = config["run"], float(ctx["seconds"])
    rule = traffic_mod.slot_rule(tr, run_cfg["num_slots"])
    print(f"[traffic] rate {tr['rate_per_s']}/s; slot rule: mean busy slots "
          f"{rule['mean_busy_slots']:.2f} + 3 sigma = {rule['needs_slots']:.2f} "
          f"of {run_cfg['num_slots']} (highest rate by the rule "
          f"{rule['max_rate_per_s']:.2f}/s) {'ok' if rule['ok'] else 'BROKEN'}",
          flush=True)
    schedule = traffic_mod.open_loop(tr, ctx["seed"], seconds, config["vocab_size"])
    for r in schedule:
        r["timeout_s"] = tr["request_timeout_s"]

    # the deployment's Serve settings (RAY_TPU_SERVE_*: the configuration's,
    # the traffic file's over them), before any process of the cluster
    # exists, so that proxy and router inherit them
    manifest.lay_serve_env(ctx)
    ray_tpu.init(num_cpus=8, resources={"TPU": cell["chips"]})
    try:
        t_ask = time.time()
        D = serve.deployment(
            BenchReplica, name="LLM", num_replicas=1,
            max_concurrent_queries=run_cfg["max_concurrent_queries"],
            ray_actor_options={"resources": {"TPU": 1}, "num_cpus": 0})
        serve.run(D.bind({k: ctx[k] for k in (
            "config", "traffic", "seed", "rehearsal", "out_dir", "control",
            "reference_file")}))
        _, port = serve.start_http_proxy()
        rec = asyncio.run(_drive("127.0.0.1", port, schedule, ctx, seconds))
        serve.shutdown()
    finally:
        ray_tpu.shutdown()
    rec["t_ask"] = t_ask
    return rec


async def _call(host, port, method, payload=None, timeout_s=300.0):
    status, body = await loadgen.post(host, port, f"/LLM/{method}", payload or {},
                                      timeout_s=timeout_s)
    if status != 200:
        raise SystemExit(f"perfbench: POST /LLM/{method} -> HTTP {status}: {body}")
    return body["result"]


async def _drive(host, port, schedule, ctx, seconds) -> dict:
    tr = ctx["traffic"]
    info = await _call(host, port, "info")
    if "fatal" in info:
        raise SystemExit(f"perfbench: the replica cannot run: {info['fatal']}")
    warm = float(tr["warm_s"])
    t_zero = time.perf_counter() + warm + 0.25   # the window opens here
    t_open = time.time() + warm + 0.25
    if ctx["trace"]:  # the replica times its own trace: no call in the queue
        a, b = tr["trace_window_s"]
        await _call(host, port, "trace_between",
                    {"start": t_open + a, "stop": t_open + b})
    rows = await loadgen.run_schedule(host, port, "/LLM/stream?stream=1",
                                      schedule, t_zero)
    t_drained = time.perf_counter() - t_zero
    if ctx["trace"]:
        traced = await _call(host, port, "trace_stop")
        print(f"[trace] the profiler ran from {traced['started'] - t_open:.2f}s to "
              f"{traced['stopped'] - t_open:.2f}s after the window opened "
              f"(asked: {a:g}s to {b:g}s) and had written its trace by "
              f"{traced['written'] - t_open:.2f}s", flush=True)
    stats = await _call(host, port, "stats", {"trace": ctx["trace"]})
    t_stats = time.perf_counter() - t_zero

    window = [r for r in rows if 0.0 <= r["due_s"] < seconds]
    for r in rows:
        r["ok"] = (r["status"] == 200 and r["error"] is None
                   and len(r["tokens"]) == r["max_new_tokens"])
    failed = [r for r in window if not r["ok"]]
    for r in failed[:5]:
        print(f"[failed] request {r['i']}: status {r['status']} error "
              f"{r['error']} tokens {len(r['tokens'])}/{r['max_new_tokens']}",
              flush=True)
    good = [r for r in window if r["ok"]]
    rnd = random.Random(ctx["seed"])
    picked = rnd.sample(good, min(tr["check_answers"], len(good)))
    by_i = {r["i"]: r for r in schedule}
    check = await _call(host, port, "check", {"samples": [
        {"prompt": by_i[r["i"]]["prompt"], "answer": r["tokens"]} for r in picked]})
    for a in check["answers"]:
        print(f"[correct] answer: {a}", flush=True)
    print(f"[after] last answer {t_drained - seconds:.1f}s after the window "
          f"closed, counters read by +{t_stats - t_drained:.1f}s, answers "
          f"compared by +{time.perf_counter() - t_zero - t_stats:.1f}s", flush=True)
    limits = tr["limits"]
    for r in rows:  # the tokens went to the check; the record keeps times
        r["n_tokens"] = len(r.pop("tokens"))
    rec = {
        "device": info["device"], "t_device": info["times"]["t_device"],
        "t_warm": info["times"]["t_warm"], "t_open": t_open,
        "compile_setup": info["compile_setup"], "rows": rows,
        "window_rows": window, "drained_s": t_drained, "replica": stats,
        "memory_peak_bytes": stats["memory_peak_bytes"],
        "attempted": len(window), "failed": len(failed),
        "compared": [
            ("token_gap_mean_spacings", check["token_gap_mean_spacings"],
             limits["token_gap_mean_spacings"]),
            ("prefill_logits_rel_err", check["prefill_logits_rel_err"],
             limits["prefill_logits_rel_err"]),
            ("wrong_length_answers", float(sum(
                1 for r in window if r["n_tokens"] != r["max_new_tokens"])), 0.0),
        ],
        "check": check,
    }
    if ctx["trace"]:
        rec["trace"] = stats.pop("trace")
    return rec
