import json, sys, glob, os
sys.path.insert(0, "/root/repo")
from perfbench.lib import traffic
for path in sorted(glob.glob("/root/repo/chiprun_out/pr39/%s*.json" % (sys.argv[1] if len(sys.argv) > 1 else "sweep_"))):
    r = json.load(open(path))
    tr, sec = r["traffic"], r["seconds"]
    win = r["window_rows"]
    offered = sum(x["max_new_tokens"] for x in win) / sec
    t0, t1 = r["t_open"], r["t_open"] + sec
    denied = sum(1 for q in r["replica"]["requests"] if "denied" in q and t0 <= q.get("submit", 0) < t1)
    busy = [n for t, n, _ in r["replica"]["steps"] if t0 <= t < t1]
    # tokens delivered inside the window: use the metric reader
    line = [l for l in open(path[:-5] + ".log") if l.startswith("{")]
    res = json.loads(line[-1]) if line else {}
    mm = res.get("metrics", {})
    delivered = mm.get("serve_tokens_per_s", {}).get("value")
    extra = {k: round(mm[k]["value"], 3) for k in ("engine.eva_step_ms_p50", "engine.batch_occupancy", "setup_s") if k in mm}
    print(os.path.basename(path), "rate", tr["rate_per_s"], "offered %.1f" % offered, "delivered", delivered, "ratio", None if not delivered else round(delivered / offered, 4),
          "denied", denied, "of", len(win), "busy mean %.2f max %d" % (sum(busy) / max(1, len(busy)), max(busy or [0])), "drained_s %.1f" % r["drained_s"], "failed", r["failed"], "correct", res.get("correct"), extra)
