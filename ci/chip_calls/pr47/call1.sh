# PR 47 call 1 (one chip): _check/parent = git archive a0741fb, _check/change = git archive $(git write-tree).
# The one-chip training cell's step compiled ON the chip from each tree and hashed (ci/chip_calls/pr47/compiled_hash.py), then the cell
# untraced parent, change, change, parent (a seed a pair), then internlm2-serve-chat parent, change (it imports models/transformer.py).
OUT=/root/repo/chiprun_out/pr47/call1; mkdir -p $OUT
for t in parent change; do
  timeout 600 python3 ci/chip_calls/pr47/compiled_hash.py _check/$t $OUT/hash_$t --only 1chip 2> $OUT/hash_$t.err | grep -a "^{" | tee $OUT/hash_$t.json
done
run() { # tree label workload seed trace
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload $3 --seed $4 --seconds 51 --trace $5 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   cp .perfbench_out/$3/last_run.json $OUT/last_run_$2.json 2>/dev/null
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-400; grep -a "^\[setup\]\|^\[chips\]\|^\[correct\]" $OUT/$2.log | cut -c 1-200)
}
run parent t_p1 mistral7b-train-1chip 4710000003 0
run change t_c1 mistral7b-train-1chip 4710000003 0
run change t_c2 mistral7b-train-1chip 4720000011 0
run parent t_p2 mistral7b-train-1chip 4720000011 0
run parent chat_p1 internlm2-serve-chat 4730000007 0
run change chat_c1 internlm2-serve-chat 4730000007 0
