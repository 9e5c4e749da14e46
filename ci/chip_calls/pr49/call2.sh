#!/bin/bash
# call 2: the new cell untraced at a provisional rate (does it agree with the
# reference, what does it deliver), then traced (call 1's trace came back
# with no plane: say what the file holds).
mkdir -p chiprun_out/pr49
for t in 0 1; do
  echo "=== change, trace $t, 1.5/s"
  python3 perfbench/run.py --workload granite4h-serve-ragsessions --seed $((2147483777 + t)) --seconds 51 --trace $t --override rate_per_s=1.5 > chiprun_out/pr49/call2_trace$t.log 2>&1
  echo "rc=$?"
  grep -a "correct\]\|\[check\]\|\[setup\]\|\[after\]\|\[traffic\]\|\[failed\]\|\[trace\]\|RESOURCE\|Traceback\|perfbench:" chiprun_out/pr49/call2_trace$t.log | cut -c1-1500 | tail -30
  tail -1 chiprun_out/pr49/call2_trace$t.log | cut -c1-6000
  cp .perfbench_out/granite4h-serve-ragsessions/last_run.json chiprun_out/pr49/call2_last_run$t.json 2>/dev/null
done
