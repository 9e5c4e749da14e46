# PR 46 call 2 (one chip; it ran after calls 3 and 4): the two one-chip cells whose configurations share the dense block's code, from
# _check/parent (git archive 44a087d) and _check/alt (git archive $(git write-tree), the tree as committed: call 4's): mistral7b-train-1chip one
# pair, internlm2-serve-chat parent, alt, alt, parent; then what tracing + lowering the four-chip step costs on this host, the chip untouched.
OUT=/root/repo/chiprun_out/pr46/call2; mkdir -p $OUT
run() { # tree label workload seed trace
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload $3 --seed $4 --seconds 51 --trace $5 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   cp .perfbench_out/$3/last_run.json $OUT/last_run_$2.json 2>/dev/null
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-330; grep -a "^\[setup\]\|^\[chips\]" $OUT/$2.log | cut -c 1-200)
}
run parent t_p1 mistral7b-train-1chip 4610000003 0
run alt t_f1 mistral7b-train-1chip 4610000003 0
run parent chat_p1 internlm2-serve-chat 4620000007 0
run alt chat_f1 internlm2-serve-chat 4620000007 0
run alt chat_f2 internlm2-serve-chat 4630000019 0
run parent chat_p2 internlm2-serve-chat 4630000019 0
for t in parent alt parent alt; do python3 ci/chip_calls/pr46/trace_cost.py _check/$t $OUT/trace_cost_$t.json 2>/dev/null | grep -a "^{"; done
