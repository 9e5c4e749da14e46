# PR 54 call 4 (one chip): call 3's pair read 15,670 -> 15,354 on a program whose lowered text is the parent's: the one-chip cell again,
# final, parent, parent, final at fresh seeds (all four warm: call 3 left both trees' entries, one and the same, in the machine's cache).
OUT=/root/repo/chiprun_out/pr54/call4; mkdir -p $OUT
run() { # tree label seed trace
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload mistral7b-train-1chip --seed $3 --seconds 51 --trace $4 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   cp .perfbench_out/mistral7b-train-1chip/last_run.json $OUT/last_run_$2.json 2>/dev/null
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-620; grep -a "^\[setup\]\|^\[chips\]" $OUT/$2.log | cut -c 1-200)
}
run final f6 5470000079 0
run parent p6 5470000079 0
run parent p7 5480000083 0
run final f7 5480000083 0
