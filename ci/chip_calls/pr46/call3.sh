# PR 46 call 3 (four chips): the tree as git would commit it (_check/final = git archive $(git write-tree); its lowered step is call 1's
# change, by hash) against _check/parent (git archive 44a087d): the cell untraced at fresh seeds parent, final, final, parent; then where
# the step's part of `compile.s` goes, one fresh process a line: tracing, lowering and the cache's read apart, parent, final, final, parent.
OUT=/root/repo/chiprun_out/pr46/call3; mkdir -p $OUT
run() { # tree label seed trace
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload mistral7b-train-4chip --seed $3 --seconds 51 --trace $4 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   cp .perfbench_out/mistral7b-train-4chip/last_run.json $OUT/last_run_$2.json 2>/dev/null
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-420; grep -a "^\[setup\]\|^\[chips\]" $OUT/$2.log | cut -c 1-200)
}
run parent p3 4640000041 0
run final f3 4640000041 0
run final f4 4650000053 0
run parent p4 4650000053 0
for t in parent final final parent; do
  timeout 600 python3 ci/chip_calls/pr46/compile_split.py _check/$t 2>$OUT/split_$t.err | grep -a "^{" | tee -a $OUT/compile_split.jsonl
done
