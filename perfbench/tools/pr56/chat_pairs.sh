#!/bin/bash
# PR 56, call 10: `internlm2-serve-chat` once more, parent, change, change,
# parent (call 9's one parent run was the first of its call into an empty
# cache and read 10% low). `_check/parent`, `_check/change` as `final.sh` says.
set -u
HERE=$(pwd); OUT=$HERE/chiprun_out/pr56; mkdir -p $OUT
run() {  # name, checkout, seed
  local t0=$(date +%s)
  ( cd $HERE/_check/$2 && python3 perfbench/run.py --workload internlm2-serve-chat --seed $3 --seconds 51 --trace 0 \
      > $OUT/chat_$1.out 2> $OUT/chat_$1.err )
  echo "== $1 rc=$? wall=$(( $(date +%s) - t0 ))s ($2 seed $3)"
  tail -n 1 $OUT/chat_$1.out | cut -c1-600
}
run warmup_parent parent 2147482040
run warmup_change change 2147482040
run parent1 parent 2147482041
run change1 change 2147482041
run change2 change 2147482042
run parent2 parent 2147482042
