#!/bin/bash
# call 5: the int8 control, three seeds (30 s windows at 1.0/s: what `correct`
# compares does not depend on the window), eight compared answers each.
mkdir -p chiprun_out/pr49/control
for seed in 2147481111 2147482222 2147483333; do
  log=chiprun_out/pr49/control/seed_$seed.log
  python3 perfbench/run.py --workload granite4h-serve-ragsessions --seed $seed --seconds 30 --trace 0 \
    --override rate_per_s=1.0 --control int8 > $log 2>&1
  echo "control seed $seed rc=$?"
  grep -a "^\[correct\] [a-z_]* =\|\[check\]" $log
  tail -1 $log | cut -c1-400
done
