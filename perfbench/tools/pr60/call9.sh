#!/bin/bash
# PR 60, call 9 (after the review), from the COMMITTED files alone (_check/rev):
# call 8's 51 s window at 1.8 req/s delivered 0.952 of offered, at 1.6 1.002:
# two 51 s windows at 1.7 decide the knee (the highest rate at which EVERY
# 51 s window reads 0.99 or more) and with it the rate, 0.65 x knee rounded
# down to 0.1. At the file's own rate: four more seeds and a second traced
# run; at another: six seeds, a traced run and both controls at that rate.
mkdir -p chiprun_out/pr60
ln -sfn "$PWD/chiprun_out" _check/rev/chiprun_out
cd _check/rev
bash perfbench/tools/pr60/sweep.sh knee51 51 1.7:2147480851 1.7:2147480852
rate=$(python3 - <<'PY'
import glob, json, math, subprocess, sys
shares = []
for f in sorted(glob.glob("chiprun_out/pr60/knee51_r1.7_*.out")):
    r = json.loads(subprocess.run([sys.executable, "perfbench/tools/pr60/readings.py", f],
                                  capture_output=True, text=True).stdout)
    shares.append(r["tokens_per_s"] / r["offered"])
knee = 1.7 if len(shares) == 2 and min(shares) >= 0.99 else 1.6
print(f"{math.floor(0.65 * knee * 10 + 1e-9) / 10:.1f}")
print(f"shares at 1.7: {shares}; knee {knee}", file=sys.stderr)
PY
)
file=$(python3 -c "import json; print(json.load(open('perfbench/traffic/mixed-queue-open-loop.json'))['rate_per_s'])")
echo "the rate by the rule: $rate; the file's: $file"
if [ "$rate" == "$file" ]; then
  bash perfbench/tools/pr60/cell.sh r_sound 0 -- 2147480803 2147480804 2147480805 2147480806
  bash perfbench/tools/pr60/cell.sh r_traced 1 -- 2147480812
else
  o="--override rate_per_s=$rate"
  bash perfbench/tools/pr60/cell.sh r${rate}_sound 0 $o -- 2147480901 2147480902 2147480903 2147480904 2147480905 2147480906
  bash perfbench/tools/pr60/cell.sh r${rate}_traced 1 $o -- 2147480911
  bash perfbench/tools/pr60/cell.sh r${rate}_int8 0 $o --control int8 -- 2147480921
  bash perfbench/tools/pr60/cell.sh r${rate}_no_window 0 $o --control no_window -- 2147480931
fi
