#!/bin/bash
# call 1: does the new cell run at all; memory, setup, correctness readings
mkdir -p chiprun_out/pr39
python3 perfbench/run.py --workload evabyte-serve-longdoc --seed 2147483659 --seconds 30 --trace 0 --override rate_per_s=0.9 > chiprun_out/pr39/call1_run.log 2>&1
echo "rc=$?"
tail -40 chiprun_out/pr39/call1_run.log
