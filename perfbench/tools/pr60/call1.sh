#!/bin/bash
# PR 60, call 1: the cell's first run on the chip, traced, placeholder limits
# (the readings are what is wanted), then one untraced run at 0.8/s.
mkdir -p chiprun_out/pr60
for spec in "2147483659 1 0.5" "2147483777 0 0.8"; do
  set -- $spec
  python3 perfbench/run.py --workload command-a-plus-serve-mixedqueue --seed $1 --seconds 51 --trace $2 \
    --override rate_per_s=$3 > chiprun_out/pr60/call1_s$1.out 2> chiprun_out/pr60/call1_s$1.err
  echo "seed $1 trace $2 rate $3 rc $?"
  grep -E "^\[(correct|check|traffic|after|setup|trace|program_spans|failed)" chiprun_out/pr60/call1_s$1.out | tail -40
  tail -n 1 chiprun_out/pr60/call1_s$1.out | cut -c1-6000
  tail -n 15 chiprun_out/pr60/call1_s$1.err
done
