"""One result line of perfbench/run.py, cut to what PR 58 reads: `correct`,
the end-to-end metrics, and the set-up metrics old and new."""
import json
import sys

KEEP = ("worker.", "actor.", "compile.")
line = json.load(open(sys.argv[1]))
m = {k: round(v["value"], 4) for k, v in line["metrics"].items()
     if k.startswith(KEEP) or "_per_s" in k or k in ("setup_s", "tpot_p50_ms")}
print(json.dumps({"correct": line["correct"], "failed": line["failed"],
                  "device": line["device"]["kind"], **m}))
