#!/bin/bash
# PR 60: runs of the cell. `cell.sh <tag> <trace> [--control <name>] -- seed ...`:
# one run a seed at the traffic file's own rate, the result line and the
# numbers compared kept under chiprun_out/pr60/ (`readings.py`).
tag=$1; trace=$2; shift 2
extra=()
while [ "$1" != "--" ]; do extra+=("$1"); shift; done
shift
mkdir -p chiprun_out/pr60
for seed in "$@"; do
  out=chiprun_out/pr60/${tag}_s${seed}
  python3 perfbench/run.py --workload command-a-plus-serve-mixedqueue --seed $seed --seconds 51 \
    --trace $trace "${extra[@]}" > $out.out 2> $out.err
  echo "$tag seed $seed trace $trace ${extra[*]} rc $?"
  grep -E "^\[(correct|check|after|setup|failed)\]" $out.out | tail -14
  tail -n 1 $out.out | cut -c1-400
  cp .perfbench_out/command-a-plus-serve-mixedqueue/last_run.json $out.last_run.json
  python3 perfbench/tools/pr60/readings.py $out.out
done
