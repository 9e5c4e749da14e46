"""tail.py <last_run.json>: what sets a serving run's `tpot_p95_ms`: the
percentiles of the per-answer token gap over the window's requests and the
requests above the 95th, each with its lengths and when it was due."""

import json
import sys

sys.path.insert(0, ".")
from perfbench.lib.stats import percentile  # noqa: E402

run = json.load(open(sys.argv[1]))
rows = [r for r in run["window_rows"] if r["ok"] and r["n_tokens"] > 1]
tp = sorted((1e3 * (r["arrivals_s"][-1] - r["arrivals_s"][0]) / (r["n_tokens"] - 1),
             r["n_tokens"], r["prompt_len"], round(r["due_s"], 2)) for r in rows)
vals = [t[0] for t in tp]
print("tpot ms over", len(vals), "answers:", " ".join(
    f"p{q}={percentile(vals, q):.3f}" for q in (5, 25, 50, 75, 90, 95, 97, 99)),
    f"mean={sum(vals) / len(vals):.3f}")
print("above p93 (ms, tokens, prompt, due):",
      " ".join(f"{t[0]:.2f}/{t[1]}/{t[2]}/{t[3]}" for t in tp[int(0.93 * len(tp)):]))
