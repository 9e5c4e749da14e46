#!/bin/bash
# PR 63: runs of the cell. `cell.sh <tag> <trace> [--control <name>] [--override k=v] -- seed ...`:
# one run a seed at the traffic file's own rate, the result line and the
# numbers compared kept under chiprun_out/pr63/ (`readings.py`). BENCH_ROOT,
# if set, is the probe root the cell is defined in.
tag=$1; trace=$2; shift 2
cell=smallthinker-serve-longanswer
extra=()
while [ "$1" != "--" ]; do extra+=("$1"); shift; done
shift
mkdir -p chiprun_out/pr63
for seed in "$@"; do
  out=chiprun_out/pr63/${tag}_s${seed}
  python3 perfbench/run.py ${BENCH_ROOT:+--root $BENCH_ROOT} --workload $cell --seed $seed \
    --seconds 51 --trace $trace "${extra[@]}" > $out.out 2> $out.err
  echo "$tag seed $seed trace $trace ${extra[*]} rc $?"
  grep -E "^\[(correct|check|after|setup|failed|trace)\]" $out.out | tail -16
  tail -n 1 $out.out | cut -c1-${LINE_CHARS:-400}
  tail -n 4 $out.err | cut -c1-300
  cp .perfbench_out/$cell/last_run.json $out.last_run.json
  python3 perfbench/tools/pr63/readings.py $out.out
done
