#!/bin/bash
# call 3: the sweep's point between 1.0 and 1.2
mkdir -p chiprun_out/pr39
for R in 1.1; do
  python3 perfbench/run.py --workload evabyte-serve-longdoc --seed 2147483659 --seconds 30 --trace 0 \
    --override rate_per_s=$R --override check_answers=2 > chiprun_out/pr39/sweep_$R.log 2>&1
  echo "rate $R rc=$?"
  cp .perfbench_out/evabyte-serve-longdoc/last_run.json chiprun_out/pr39/sweep_$R.json
  grep -E "^\[setup\]|^\[after\]|^\[failed\]" chiprun_out/pr39/sweep_$R.log | cut -c1-400
done
