#!/bin/bash
# PR 56, second session, the last call: the COMMITTED files alone, under the
# COMMITTED limits. Before it, here:
#   git add -A; rm -rf _check/change _check/parent_new; mkdir -p _check/change _check/parent_new
#   git archive $(git write-tree) | tar -x -C _check/change
#   git archive 72d3d7c | tar -x -C _check/parent_new && cp BENCHMARK.json _check/parent_new/ \
#     && cp -r perfbench/. _check/parent_new/perfbench/ && cp -r tests/perfbench/. _check/parent_new/tests/perfbench/
#   chiprun --timeout 3300 -- bash perfbench/tools/pr56/final2.sh
# Seven untraced seeds and one traced one of the new cell; the parent under this
# PR's benchmark files must end it at once. The program under `ray_tpu/` is the
# first session's (call 9 ran the other cells against the parent).
# Result lines: chiprun_out/pr56/final2_<name>.out / .err / .json
set -u
HERE=$(pwd)
OUT=$HERE/chiprun_out/pr56; mkdir -p $OUT
CELL=keye-vl2-serve-docqa
run() {  # name, checkout, seed, trace
  local t0=$(date +%s)
  ( cd $HERE/_check/$2 && python3 perfbench/run.py --workload $CELL --seed $3 --seconds 51 --trace $4 \
      > $OUT/final2_$1.out 2> $OUT/final2_$1.err
    rc=$?
    cp .perfbench_out/$CELL/last_run.json $OUT/final2_$1.json 2>/dev/null
    exit $rc )
  echo "== $1 rc=$? wall=$(( $(date +%s) - t0 ))s ($2 seed $3 trace $4)"
  grep -E "^\[(correct|trace|check)\]" $OUT/final2_$1.out | grep -v "answer:" | cut -c1-200
  tail -n 1 $OUT/final2_$1.out | cut -c1-${5:-700}
}
run parent_cannot parent_new 2147483201 0
for i in 1 2 3 4 5 6 7; do run u$i change $((2147483210 + i)) 0; done
run t1 change 2147483221 1 4000

