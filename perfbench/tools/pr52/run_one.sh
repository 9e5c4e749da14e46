#!/bin/bash
# run_one.sh <tag> <cell> <seed> <trace> [--root <dir>]: one run of one cell
# at the benchmark's 51 s from the current directory's checkout, its whole
# output kept under chiprun_out/pr52/<tag>.log, and what PERF.md quotes of
# it printed: the settings in force, the numbers compared, the result line
# and `tools/probe.py read`'s line of the run's record.
tag=$1 cell=$2 seed=$3 trace=$4; shift 4
out=${PR52_OUT:-$PWD/chiprun_out/pr52}; mkdir -p $out
t0=$(date +%s)
python3 perfbench/run.py --workload $cell --seed $seed --seconds 51 --trace $trace "$@" \
  > $out/$tag.log 2> $out/$tag.err
rc=$?
echo "== $tag $cell seed=$seed trace=$trace rc=$rc took $(( $(date +%s) - t0 ))s"
grep -a "^\[traffic\]\|^\[correct\] [a-z_]* =\|^\[after\]\|^\[failed\]\|^\[setup\]\|^\[token_path\]\|^\[trace\]\|NOT OK\|perfbench:" $out/$tag.log | cut -c1-400 | head -24
grep -a "Traceback\|RESOURCE_EXHAUSTED\|perfbench:\|\[chips\] waited" $out/$tag.err | cut -c1-300 | head -5
tail -n 1 $out/$tag.log | cut -c1-6000
python3 perfbench/tools/probe.py read .perfbench_out/$cell/last_run.json 2>&1 | tail -n 1 | cut -c1-1500
