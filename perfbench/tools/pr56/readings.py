"""PR 56: the compared numbers of kept run records, a line a run, then for
each number the sound runs' highest, the `int8` runs' lowest, their geometric
middle, and how many of the runs lie inside the COMMITTED limits
(`perfbench/traffic/docqa-open-loop.json`), whatever limits they ran under.

    python3 perfbench/tools/pr56/readings.py chiprun_out/pr56/{p1,s2,s3}.json -- chiprun_out/pr56/{p1i,i2,i3}.json
"""

import json
import math
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
NAMES = ("token_gap_mean_spacings", "prefill_logits_rel_err", "route_margin_max",
         "select_margin_max")


def numbers(path):
    rec = json.load(open(path))
    got = {n: v for n, v, *_ in rec["compared"]}
    ran_under = {n: lim for n, _, lim in rec["compared"]}
    return rec, got, ran_under


def main(argv):
    cut = argv.index("--") if "--" in argv else len(argv)
    groups = {"sound": argv[:cut], "int8": argv[cut + 1:]}
    limits = json.load(open(os.path.join(
        ROOT, "perfbench", "traffic", "docqa-open-loop.json")))["limits"]
    read = {}
    for kind, paths in groups.items():
        for p in paths:
            rec, got, ran_under = numbers(p)
            read.setdefault(kind, []).append(got)
            inside = all(got[n] <= limits[n] for n in NAMES) and not rec["failed"]
            print(f"{kind:5} {os.path.basename(p):22} "
                  + " ".join(f"{got[n]:.5g}" for n in NAMES)
                  + f" | failed {rec['failed']} of {rec['attempted']}"
                  + f" | ran under {[ran_under[n] for n in NAMES]}"
                  + f" | inside the committed limits: {inside}")
    for n in NAMES:
        hi = max(g[n] for g in read.get("sound", [{n: float('nan')}]))
        line = f"{n}: sound highest {hi:.5g}"
        if read.get("int8"):
            lo = min(g[n] for g in read["int8"])
            line += f", int8 lowest {lo:.5g}, geometric middle {math.sqrt(hi * lo):.4g}"
        print(line + f"; committed {limits[n]}")


if __name__ == "__main__":
    main(sys.argv[1:])
