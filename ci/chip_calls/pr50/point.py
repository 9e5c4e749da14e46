"""`point.py <last_run.json> <log of the run>`: one line of what a run of a
list-form cell left of its SET-UP: `setup_s` and its parts on the host clock
(process start -> worker asked -> first device -> programs warm -> window
open), jax's own seconds tracing, lowering and compiling or reading its
cache (`compile.s`), how the cache answered, and what the cell delivered."""
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)
from perfbench.lib.manifest import load_py

run = json.load(open(sys.argv[1]))
line = json.loads(open(sys.argv[2]).read().strip().splitlines()[-1])
m = {k: v["value"] for k, v in line["metrics"].items()}
t = lambda a, b: round(run[b] - run[a], 2)
own = ("engine.hybrid_step_ms_p50", "kernels.hybrid_decode_hbm_share",
       "engine.mtp_step_ms_p50", "kernels.mla_moe_decode_hbm_share",
       "engine.compiles_in_window", "compile.s", "worker.spawn_to_device_s")
# what the benchmark reads from OUTSIDE the program is in every run's record,
# traced or not: the step's and the prompt passes' wall time, the token gap
outside = {}
for name in ("engine.decode_step_ms_p50", "engine.batch_occupancy", "serve.ttft_p50_ms",
             "serve.token_gap_all_p95_ms", "tpot_p95_ms", "loadgen.late_p95_ms"):
    try:
        value = load_py(os.path.join(ROOT, "perfbench", "metrics", name + ".py")).read(run)
    except Exception as e:   # a reader that wants what the record dropped
        value = repr(e)[:60]
    outside[name] = round(value, 3) if isinstance(value, float) else value
t0 = run["t_open"]
passes = [1e3 * (b - a) for a, b, _ in
          run.get("replica", {}).get("spans", {}).get("bench.prefill", []) if a >= t0]
if passes:
    outside["prompt_pass_ms"] = {"n": len(passes), "p50": round(statistics.median(passes), 2),
                                 "mean": round(statistics.fmean(passes), 2)}
gaps = [(r["arrivals_s"][-1] - r["arrivals_s"][0]) / (len(r["arrivals_s"]) - 1)
        for r in run.get("window_rows", []) if r.get("ok") and len(r["arrivals_s"]) > 1]
if gaps:
    outside["answer_mean_gap_ms_p50"] = round(1e3 * statistics.median(gaps), 3)
# the window is one period: what streams in from before it should be what
# streams out of its end; the two, in tokens (tokens/s x seconds is counted
# over ALL rows, `window_rows` are the window's own requests)
rows = [r for r in run.get("window_rows", []) if r.get("ok")]
if rows and m.get("serve_tokens_per_s") is not None:
    own_in = sum(1 for r in rows for t in r["arrivals_s"] if 0.0 <= t < run["seconds"])
    outside["tokens_streamed_in_from_before"] = round(
        m["serve_tokens_per_s"] * run["seconds"]) - own_in
    outside["tokens_streamed_out_past_the_end"] = sum(
        1 for r in rows for t in r["arrivals_s"] if t >= run["seconds"])
print(json.dumps({
    "setup_s": round(run["t_open"] - run["t_start"], 2),
    "start_to_ask": t("t_start", "t_ask"), "ask_to_device": t("t_ask", "t_device"),
    "device_to_warm": t("t_device", "t_warm"), "warm_to_open": t("t_warm", "t_open"),
    "compile": {k: round(v, 2) for k, v in run["compile_setup"].items()},
    "serve_tokens_per_s": m.get("serve_tokens_per_s"),
    "correct": line["correct"], "failed": line["failed"],
    "compared": {n: round(v, 5) for n, v, _ in run["compared"]},
    "peak_GB": round(line["device"]["memory_peak_bytes"] / 1e9, 3),
    **{k: round(m[k], 3) for k in own if k in m}, "outside": outside}))
