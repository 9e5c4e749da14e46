#!/bin/bash
# PR 63, call 1: (a) the PARENT's checkout (_check/parent63: `git archive` of
# the parent commit with this PR's BENCHMARK.json and perfbench/ laid over it,
# as the driver lays them) must refuse the new cell in seconds; (b) the cell's
# first runs on the chip in its probe root, placeholder limits (the readings
# are what is wanted): one traced at 0.8/s, one untraced at 1.0/s.
mkdir -p chiprun_out/pr63
t0=$(date +%s.%N)
(cd _check/parent63 && timeout 120 python3 perfbench/run.py --workload smallthinker-serve-longanswer \
   --seed 2147483659 --seconds 51 --trace 0 > ../../chiprun_out/pr63/parent_refuses.out 2>&1; echo "parent rc $?")
t1=$(date +%s.%N)
echo "the parent's checkout answered in $(python3 -c "print(round($t1 - $t0, 2))") s:"
tail -n 3 chiprun_out/pr63/parent_refuses.out
export BENCH_ROOT=_check/sthink
LINE_CHARS=7000 bash perfbench/tools/pr63/cell.sh first_traced 1 --override rate_per_s=0.8 -- 2147483659
bash perfbench/tools/pr63/cell.sh first 0 --override rate_per_s=1.0 -- 2147483777
