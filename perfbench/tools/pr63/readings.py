"""`readings.py <glob of .out files>`: one line a run of the SmallThinker
cell from its result line (and, where `<run>.last_run.json` lies beside it,
from that): rate, seed, offered -> delivered tokens/s, occupancy, the step's
median, the longest, median and p95 wait for a first token, the numbers
compared (`tools/pr60/readings.py` with this cell's step metric)."""
import glob
import json
import os
import sys

for f in sorted(p for pat in sys.argv[1:] for p in glob.glob(pat)):
    lines = [l for l in open(f) if l.startswith("{")]
    if not lines:
        print(f, "NO RESULT LINE")
        continue
    d = json.loads(lines[-1])
    m = {k: v["value"] for k, v in d["metrics"].items()}
    out = {"run": os.path.basename(f)[:-4], "correct": d["correct"],
           "attempted": d["attempted"], "failed": d["failed"],
           "tokens_per_s": round(m.get("serve_tokens_per_s", 0), 3),
           "setup_s": round(m.get("setup_s", 0), 1),
           "peak_GB": round(d["device"]["memory_peak_bytes"] / 1e9, 3),
           **{k: round(v["value"], 5) for k, v in d["compared"].items()
              if k.endswith(("err", "max", "spacings"))}}
    for k in ("engine.batch_occupancy", "engine.sthink_step_ms_p50",
              "moe.sthink_experts_touched_share", "swa.sthink_wrapped_slots_share"):
        if k in m:
            out[k.split(".")[1]] = round(m[k], 2)
    side = f[:-4] + ".last_run.json"
    if os.path.isfile(side):
        lr = json.load(open(side))
        win = lr["window_rows"]
        waits = sorted(r["arrivals_s"][0] - r["sent_s"] for r in win if r.get("arrivals_s"))
        out.update(offered=round(sum(r["max_new_tokens"] for r in win) / lr["seconds"], 2),
                   ttft_max_s=round(waits[-1], 2), ttft_p50_s=round(waits[len(waits) // 2], 2),
                   ttft_p95_s=round(waits[int(0.95 * (len(waits) - 1))], 2),
                   drained_s=round(lr["drained_s"], 1))
    print(json.dumps(out))
