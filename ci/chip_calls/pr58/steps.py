"""A training run's kept record: how its steps' durations spread (a run that
lost steps to ONE stall reads differently from one whose every step was slow)."""
import json
import sys

rec = json.load(open(sys.argv[1]))
ends = rec["step_end_s"]
durs = sorted(b - a for a, b in zip([0.0] + ends, ends))
q = lambda p: round(1e3 * durs[min(len(durs) - 1, int(p * len(durs)))], 2)
print(json.dumps({"steps": len(durs), "ms_min": q(0), "p50": q(0.5), "p90": q(0.9),
                  "p99": q(0.99), "max": q(1.0), "over_300ms": sum(d > 0.3 for d in durs)}))
