# PR 45 call 5 (one chip): the tree as git would commit it (_check/final = git archive $(git write-tree)) against the parent
# (_check/parent = f1b97d8): the chat cell at four fresh seeds P F F P, one traced run of it and one of Jamba's cell
OUT=/root/repo/chiprun_out/pr45/call5; mkdir -p $OUT
run() { # tree label workload seed trace
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload $3 --seed $4 --seconds 51 --trace $5 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   cp .perfbench_out/$3/last_run.json $OUT/last_run_$2.json 2>/dev/null
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-330; grep -a "^\[setup\]\|^\[chips\]" $OUT/$2.log | cut -c 1-200)
}
run final warm internlm2-serve-chat 3200000001 0
for S in 3300000011 3400000017 3500000019 3600000021; do
  run parent p_${S}_a internlm2-serve-chat $S 0
  run final f_${S}_a internlm2-serve-chat $S 0
  run final f_${S}_b internlm2-serve-chat $S 0
  run parent p_${S}_b internlm2-serve-chat $S 0
done
run final f_traced internlm2-serve-chat 3700000023 1
run final jf_traced jamba2-serve-chat-burst 3800000029 1
run final jf_a jamba2-serve-chat-burst 3900000031 0
