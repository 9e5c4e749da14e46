# PR 61 call 7 (one chip): the one-chip cell that shares `loss_fn` and the dense block's code (its step's lowered text is the parent's, by
# `ci/chip_calls/pr54/lowered_hash.py`), _check/parent against _check/final at fresh seeds: parent, final, final, parent.
OUT=/root/repo/chiprun_out/pr61/call7; mkdir -p $OUT
run() { # tree label seed
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload mistral7b-train-1chip --seed $3 --seconds 51 --trace 0 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-700)
}
run parent p1 6110000021
run final f1 6110000021
run final f2 6120000037
run parent p2 6120000037
