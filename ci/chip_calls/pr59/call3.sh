# PR 59 call 3 (one chip): the one-chip cell that shares the dense block's code (its step's lowered text is the parent's, by
# `lowered_hash.py`), _check/parent against _check/final at fresh seeds: parent, final, final, parent.
OUT=/root/repo/chiprun_out/pr59/call3; mkdir -p $OUT
run() { # tree label seed
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload mistral7b-train-1chip --seed $3 --seconds 51 --trace 0 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-700)
}
run parent p1 5910000043
run final f1 5910000043
run final f2 5920000057
run parent p2 5920000057
