#!/bin/bash
# PR 56, the last call: the COMMITTED files alone. Before it, here:
#   git add -A; rm -rf _check/change _check/parent; mkdir -p _check/change _check/parent
#   git archive $(git write-tree) | tar -x -C _check/change
#   git archive 72d3d7c | tar -x -C _check/parent        # the parent, its own benchmark files
#   mkdir -p _check/parent_new && git archive 72d3d7c | tar -x -C _check/parent_new \
#     && cp BENCHMARK.json _check/parent_new/ && cp -r perfbench/. _check/parent_new/perfbench/ \
#     && cp -r tests/perfbench/. _check/parent_new/tests/perfbench/   # the parent under this PR's benchmark files
#   chiprun --timeout 3550 -- bash perfbench/tools/pr56/final.sh
# Result lines: chiprun_out/pr56/final_<name>.out / .err
set -u
HERE=$(pwd)                      # the checkout's root: chiprun starts the command there
OUT=$HERE/chiprun_out/pr56; mkdir -p $OUT
COLD=${TMPDIR:-$HERE/_check}/pr56_cold_cache   # an empty compile cache of the cold run's own
run() {  # name, checkout, cell, seed, trace, [env]
  local t0=$(date +%s)
  ( cd $HERE/_check/$2 && env ${6:-A=1} python3 perfbench/run.py --workload $3 --seed $4 --seconds 51 --trace $5 \
      > $OUT/final_$1.out 2> $OUT/final_$1.err )
  echo "== $1 rc=$? wall=$(( $(date +%s) - t0 ))s ($2 $3 seed $4 trace $5 ${6:-})"
  grep -E "^\[correct\] [a-z_]+ =" $OUT/final_$1.out | cut -c1-200
  tail -n 1 $OUT/final_$1.out | cut -c1-2500
}
run parent_cannot parent_new keye-vl2-serve-docqa 2147482001 0
run keye_cold   change keye-vl2-serve-docqa 2147482011 0 JAX_COMPILATION_CACHE_DIR=$COLD
run keye_warm   change keye-vl2-serve-docqa 2147482012 0
run keye_traced change keye-vl2-serve-docqa 2147482013 1
run chat_parent    parent internlm2-serve-chat 2147482021 0
run chat_change    change internlm2-serve-chat 2147482021 0
run granite_change change granite4h-serve-ragsessions 2147482031 0
run granite_parent parent granite4h-serve-ragsessions 2147482031 0
ls $HERE/_check/change | tr '\n' ' '
