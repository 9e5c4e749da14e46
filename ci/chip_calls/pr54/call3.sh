# PR 54 call 3 (one chip): the one-chip training cell, whose step shares the dense block's modules and takes none of parallel/tp.py
# (fsdp 1: its lowered text hashes as the parent's): _check/parent against _check/final, untraced, one pair (the first of a tree compiles cold).
OUT=/root/repo/chiprun_out/pr54/call3; mkdir -p $OUT
run() { # tree label seed trace
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload mistral7b-train-1chip --seed $3 --seconds 51 --trace $4 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   cp .perfbench_out/mistral7b-train-1chip/last_run.json $OUT/last_run_$2.json 2>/dev/null
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-620; grep -a "^\[setup\]\|^\[chips\]" $OUT/$2.log | cut -c 1-200)
}
run parent p5 5460000067 0
run final f5 5460000067 0
