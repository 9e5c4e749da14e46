#!/bin/bash
# call 4: six seeds of the cell as committed (51 s, the traffic file's rate),
# then one traced run of it.
mkdir -p chiprun_out/pr49/seeds
for seed in $SEEDS; do
  log=chiprun_out/pr49/seeds/seed_$seed.log
  python3 perfbench/run.py --workload granite4h-serve-ragsessions --seed $seed --seconds 51 --trace 0 $EXTRA > $log 2>&1
  echo "seed $seed rc=$?"
  tail -1 $log > chiprun_out/pr49/seeds/seed_$seed.line
  cp .perfbench_out/granite4h-serve-ragsessions/last_run.json chiprun_out/pr49/seeds/seed_$seed.json
  python3 ci/chip_calls/pr49/point.py chiprun_out/pr49/seeds/seed_$seed.json chiprun_out/pr49/seeds/seed_$seed.line
  grep -a "\[failed\]\|Traceback\|RESOURCE\|NOT OK" $log | head -3
done
if [ -n "$TRACED" ]; then
  log=chiprun_out/pr49/seeds/traced.log
  python3 perfbench/run.py --workload granite4h-serve-ragsessions --seed $TRACED --seconds 51 --trace 1 $EXTRA > $log 2>&1
  echo "traced rc=$?"
  grep -a "\[trace\]\|\[setup\]" $log | cut -c1-600
  tail -1 $log | cut -c1-5000
  cp .perfbench_out/granite4h-serve-ragsessions/last_run.json chiprun_out/pr49/seeds/traced.json
fi
