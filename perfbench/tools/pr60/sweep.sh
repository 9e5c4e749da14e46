#!/bin/bash
# PR 60: the sweep for the knee. `sweep.sh <tag> <seconds> rate:seed ...`: one
# untraced run a point, the traffic file's rate overridden; offered and
# delivered tokens/s, busy slots, the step's median and the longest wait for a
# first token are read off each run's result line and last_run.json
# (`readings.py`).
tag=$1; seconds=$2; shift 2
mkdir -p chiprun_out/pr60
for point in "$@"; do
  rate=${point%%:*}; seed=${point##*:}
  out=chiprun_out/pr60/${tag}_r${rate}_s${seed}
  python3 perfbench/run.py --workload command-a-plus-serve-mixedqueue --seed $seed --seconds $seconds \
    --trace 0 --override rate_per_s=$rate > $out.out 2> $out.err
  echo "rate $rate seed $seed rc $?"
  grep -E "^\[(correct|check|after|setup|failed)\]" $out.out | tail -12
  cp .perfbench_out/command-a-plus-serve-mixedqueue/last_run.json $out.last_run.json
  python3 perfbench/tools/pr60/readings.py $out.out
done
