#!/bin/bash
# PR 63: the sweep for the knee. `sweep.sh <tag> <seconds> rate:seed ...`: one
# untraced run a point, the traffic file's rate overridden and warm_s 45 laid
# over its 20 (near the knee an answer lasts up to 30 s: a window with no queue
# delivers what it is offered only behind warm traffic that outlasts one); offered and
# delivered tokens/s, busy slots, the step's median and the waits for a first
# token are read off each run's result line and last_run.json (`readings.py`).
# BENCH_ROOT, if set, is the probe root the cell is defined in.
tag=$1; seconds=$2; shift 2
cell=smallthinker-serve-longanswer
mkdir -p chiprun_out/pr63
for point in "$@"; do
  rate=${point%%:*}; seed=${point##*:}
  out=chiprun_out/pr63/${tag}_r${rate}_s${seed}
  python3 perfbench/run.py ${BENCH_ROOT:+--root $BENCH_ROOT} --workload $cell --seed $seed \
    --seconds $seconds --trace 0 --override rate_per_s=$rate --override warm_s=45 > $out.out 2> $out.err
  echo "rate $rate seed $seed rc $?"
  grep -E "^\[(correct|check|after|setup|failed)\]" $out.out | tail -12
  cp .perfbench_out/$cell/last_run.json $out.last_run.json
  python3 perfbench/tools/pr63/readings.py $out.out
done
