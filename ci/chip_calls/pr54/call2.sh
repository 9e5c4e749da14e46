# PR 54 call 2 (four chips): the tree as git would commit it (_check/final = git archive $(git write-tree); its lowered step is call 1's
# change, by hash) against _check/parent (git archive 4ba736f): the cell untraced at fresh seeds parent, final, final, parent.
OUT=/root/repo/chiprun_out/pr54/call2; mkdir -p $OUT
run() { # tree label seed trace
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload mistral7b-train-4chip --seed $3 --seconds 51 --trace $4 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   cp .perfbench_out/mistral7b-train-4chip/last_run.json $OUT/last_run_$2.json 2>/dev/null
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-620; grep -a "^\[setup\]\|^\[chips\]" $OUT/$2.log | cut -c 1-200)
}
run parent p3 5440000041 0
run final f3 5440000041 0
run final f4 5450000053 0
run parent p4 5450000053 0
