"""`point.py <last_run.json> <result line file>`: one line of a sweep or of a
seed's run from what `perfbench/run.py` left: offered and delivered
tokens/s, requests that found no slot, busy slots, the step's and the prompt
pass's medians, what `correct` compared, the check's seconds, peak memory."""
import json
import sys

run = json.load(open(sys.argv[1]))
line = json.loads(open(sys.argv[2]).read().strip().splitlines()[-1])
sec = run["seconds"]
rows = run["window_rows"]
offered = sum(r["max_new_tokens"] for r in rows) / sec
reqs = run["replica"]["requests"]
denied = sum(1 for q in reqs if "denied" in q and q.get("submit", 0) >= run["t_open"])
m = {k: v["value"] for k, v in line["metrics"].items()}
spans = run["replica"].get("spans", {})
prefill = sorted(1e3 * (b - a) for a, b, _ in spans.get("bench.prefill", []))
out = {
    "rate": run["traffic"]["rate_per_s"], "seconds": sec, "requests": len(rows),
    "offered_tok_s": round(offered, 2),
    "delivered_tok_s": round(m.get("serve_tokens_per_s", float("nan")), 2),
    "ratio": round(m.get("serve_tokens_per_s", float("nan")) / offered, 4),
    "no_slot": denied, "failed": line["failed"], "correct": line["correct"],
    "busy_slots": round(m.get("engine.batch_occupancy", float("nan")) / 100
                        * run["config"]["run"]["num_slots"], 2),
    "ssd_step_ms_p50": round(m.get("engine.ssd_step_ms_p50", float("nan")), 2),
    "tok_per_held_expert": round(m.get("moe.ssd_tokens_per_held_expert", float("nan")), 3),
    "experts_touched_pct": round(m.get("moe.ssd_experts_touched_share", float("nan")), 1),
    "setup_s": round(m.get("setup_s", float("nan")), 1),
    "drained_s": round(run["drained_s"] - sec, 1),
    "compared": {n: round(v, 5) for n, v, _ in run["compared"]},
    "check_s": run["check"].get("check_s"),
    "peak_GB": round(line["device"]["memory_peak_bytes"] / 1e9, 3),
}
print(json.dumps(out))
