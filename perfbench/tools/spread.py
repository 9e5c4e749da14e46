"""Read the result lines of several runs (log files whose last `{...}` line
is a result) and print, per metric: the values, the median, the spread as
the contract defines it (quartile distance over the median, Python's
`statistics.quantiles`), and the driver's tightness reading (range after
leaving out the run farthest from the median).

    python perfbench/tools/spread.py chiprun_out/sets/serve_A_*.log
"""

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from perfbench.lib.stats import quartile_spread, trimmed_range  # noqa: E402


def result_line(path):
    for line in reversed(open(path, errors="replace").read().splitlines()):
        if line.startswith("{") and '"metrics"' in line:
            return json.loads(line)
    return None


def main(paths):
    rows = [(p, result_line(p)) for p in paths]
    for p, r in rows:
        if r is None:
            print(f"NO RESULT in {p}")
    rows = [(p, r) for p, r in rows if r]
    print(f"{len(rows)} runs; correct: {[r['correct'] for _, r in rows]}; "
          f"failed: {[r['failed'] for _, r in rows]}; attempted: "
          f"{[r['attempted'] for _, r in rows]}")
    for name in rows[0][1]["metrics"]:
        vals = [r["metrics"][name]["value"] for _, r in rows if name in r["metrics"]]
        line = f"{name}: median {statistics.median(vals):.6g}"
        if len(vals) >= 4:
            line += (f"  quartile spread {100 * quartile_spread(vals):.3f}%"
                     f"  trimmed range {100 * trimmed_range(vals):.3f}%")
        print(line, " values", " ".join(f"{v:.6g}" for v in vals))
    peak = [r["device"]["memory_peak_bytes"] for _, r in rows]
    print("memory_peak_bytes", sorted(set(peak)))


if __name__ == "__main__":
    main(sys.argv[1:])
