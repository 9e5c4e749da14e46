#!/bin/bash
# call 4: six seeds at 0.8 x the knee (0.8/s), the full window
mkdir -p chiprun_out/pr39
R=${1:-0.8}
for S in 2147483777 2147484001 2147485003 2147486011 2147487017 2147488019; do
  python3 perfbench/run.py --workload evabyte-serve-longdoc --seed $S --seconds 51 --trace 0 \
    --override rate_per_s=$R > chiprun_out/pr39/rate${R}_$S.log 2>&1
  echo "seed $S rc=$?"
  cp .perfbench_out/evabyte-serve-longdoc/last_run.json chiprun_out/pr39/rate${R}_$S.json
  grep -E "^\[setup\]|^\[after\]|^\[failed\]|^\[correct\] [a-z_]+ =|^\[correct\] the" chiprun_out/pr39/rate${R}_$S.log | cut -c1-300
done
