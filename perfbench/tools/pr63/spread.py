"""`spread.py <glob of .out files>`: the runs' `serve_tokens_per_s`, their
spread as the driver reads it (the distance between the first and third
quartile of `statistics.quantiles(values, n=4)` over the median), and the
same with the run farthest from the median left out (PERF.md section 7 item
6: what admits a serving cell at or under one request a second). The last
line is `SPREAD <all> <farthest left out>` in percent."""
import glob
import json
import statistics
import sys


def spread(values):
    q = statistics.quantiles(values, n=4)
    return 100.0 * (q[2] - q[0]) / statistics.median(values)


values = []
for f in sorted(p for pat in sys.argv[1:] for p in glob.glob(pat)):
    lines = [l for l in open(f) if l.startswith("{")]
    if lines:
        values.append(json.loads(lines[-1])["metrics"]["serve_tokens_per_s"]["value"])
print("serve_tokens_per_s", values)
if len(values) >= 4:
    med = statistics.median(values)
    kept = sorted(values, key=lambda v: abs(v - med))[:-1]
    print(f"SPREAD {spread(values):.4f} {spread(kept):.4f}")
