#!/bin/bash
# call 3: the rate sweep on the finished change: 30 s windows, one seed a
# point, two compared answers a point (the six seeds of call 4 compare eight).
mkdir -p chiprun_out/pr49/sweep
for rate in $RATES; do
  log=chiprun_out/pr49/sweep/rate_$rate.log
  python3 perfbench/run.py --workload granite4h-serve-ragsessions --seed $((${SEED0:-2147480000} + ${rate/./})) --seconds 30 --trace 0 \
    --override rate_per_s=$rate --override check_answers=2 > $log 2>&1
  echo "rate $rate rc=$?"
  tail -1 $log > chiprun_out/pr49/sweep/rate_$rate.line
  cp .perfbench_out/granite4h-serve-ragsessions/last_run.json chiprun_out/pr49/sweep/rate_$rate.json
  python3 ci/chip_calls/pr49/point.py chiprun_out/pr49/sweep/rate_$rate.json chiprun_out/pr49/sweep/rate_$rate.line
  grep -a "\[failed\]\|Traceback\|RESOURCE" $log | head -3
done
