#!/bin/bash
# call 1: the parent on the new cell (must fail at once), then the new cell
# once, traced, at a provisional rate: does it run, fit and agree?
mkdir -p chiprun_out/pr49
echo "=== parent on the new cell"; date +%s.%N
(cd _check/parent && timeout 120 python3 perfbench/run.py --workload granite4h-serve-ragsessions --seed 1 --seconds 51 --trace 0; echo "parent rc=$?") 2>&1 | tail -5
date +%s.%N
echo "=== change, traced, 1.5/s"
python3 perfbench/run.py --workload granite4h-serve-ragsessions --seed 2147483777 --seconds 51 --trace 1 --override rate_per_s=1.5 > chiprun_out/pr49/call1_trace.log 2>&1
echo "rc=$?"
grep -a "correct\]\|\[check\]\|\[setup\]\|\[after\]\|\[traffic\]\|\[failed\]\|Error\|error\|RESOURCE\|Traceback" chiprun_out/pr49/call1_trace.log | tail -40
tail -1 chiprun_out/pr49/call1_trace.log
cp .perfbench_out/granite4h-serve-ragsessions/last_run.json chiprun_out/pr49/call1_last_run.json 2>/dev/null
