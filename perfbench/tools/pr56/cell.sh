#!/bin/bash
# PR 56: runs of the Keye cell from the probe root `_check/pr56` (made here by
#   python3 perfbench/tools/pr56/entries.py probe && python3 perfbench/tools/pr56/entries.py root _check/pr56)
# or, with ROOT_DIR=., from the checkout itself. One line an argument:
#   name:seed:trace[:rate[:control[:seconds[:check_answers]]]]
#   chiprun --timeout 3000 -- bash perfbench/tools/pr56/cell.sh first:2147480301:0:0.7
# Result lines, stdout and stderr: chiprun_out/pr56/<name>.out / .err / .json
set -u
OUT=$(pwd)/chiprun_out/pr56; mkdir -p $OUT
ROOT_DIR=${ROOT_DIR:-_check/pr56}
CELL=${CELL:-keye-vl2-serve-docqa}
for spec in "$@"; do
  IFS=: read -r name seed trace rate control seconds answers <<< "$spec"
  args="--root $ROOT_DIR --workload $CELL --seed $seed --seconds ${seconds:-51} --trace $trace"
  [ -n "${rate:-}" ] && args="$args --override rate_per_s=$rate"
  [ -n "${control:-}" ] && args="$args --control $control"
  [ -n "${answers:-}" ] && args="$args --override check_answers=$answers"
  t0=$(date +%s)
  python3 perfbench/run.py $args > $OUT/$name.out 2> $OUT/$name.err
  rc=$?
  echo "== $name rc=$rc wall=$(( $(date +%s) - t0 ))s ($args)"
  grep -E "^\[(correct|check|after|setup|traffic)\]" $OUT/$name.out | cut -c1-400
  tail -n 1 $OUT/$name.out | cut -c1-3000
  cp .perfbench_out/$CELL/last_run.json $OUT/$name.json 2>/dev/null
  python3 perfbench/tools/probe.py read $OUT/$name.json 2>/dev/null | cut -c1-1500
  [ $rc -ne 0 ] && tail -n 15 $OUT/$name.err | cut -c1-500
done
exit 0   # a run's own exit code is in its `== name rc=` line
