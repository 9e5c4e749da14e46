# PR 47 call 2 (four chips): the four-chip training cell's step compiled ON the chips from each tree and hashed, then the cell untraced
# parent, change, change, parent (a seed a pair). Nothing else: the one-chip phases are call 1's.
OUT=/root/repo/chiprun_out/pr47/call2; mkdir -p $OUT
for t in parent change; do
  timeout 900 python3 ci/chip_calls/pr47/compiled_hash.py _check/$t $OUT/hash_$t --only 4chip 2> $OUT/hash_$t.err | grep -a "^{" | tee $OUT/hash_$t.json
done
run() { # tree label workload seed trace
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload $3 --seed $4 --seconds 51 --trace $5 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   cp .perfbench_out/$3/last_run.json $OUT/last_run_$2.json 2>/dev/null
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-400; grep -a "^\[setup\]\|^\[chips\]\|^\[correct\]" $OUT/$2.log | cut -c 1-200)
}
run parent t4_p1 mistral7b-train-4chip 4740000003 0
run change t4_c1 mistral7b-train-4chip 4740000003 0
run change t4_c2 mistral7b-train-4chip 4750000011 0
run parent t4_p2 mistral7b-train-4chip 4750000011 0
