# PR 61 call 7b (one chip): call 7 again with the runs' records kept (its `final` f1 read 14,259 where the three others read 15,557-15,668,
# on a machine whose parent runs lay 0.7% apart themselves): final, parent, final, parent at fresh seeds.
OUT=/root/repo/chiprun_out/pr61/call7b; mkdir -p $OUT
run() { # tree label seed
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload mistral7b-train-1chip --seed $3 --seconds 51 --trace 0 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   cp .perfbench_out/mistral7b-train-1chip/last_run.json $OUT/last_run_$2.json 2>/dev/null
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-300)
}
run final f3 6150000071
run parent p3 6150000071
run final f4 6160000087
run parent p4 6160000087
