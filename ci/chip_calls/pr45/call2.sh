# PR 45 call 2 (one chip): parent (_check/parent = f1b97d8) against change (_check/change = git archive $(git write-tree)):
# the chat cell at four seeds P C C P, one traced pair, Jamba's cell one untraced and one traced pair; then the repair's check (ii)
OUT=/root/repo/chiprun_out/pr45/call2; mkdir -p $OUT
run() { # tree label workload seed trace
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload $3 --seed $4 --seconds 51 --trace $5 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   cp .perfbench_out/$3/last_run.json $OUT/last_run_$2.json 2>/dev/null
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-330; grep -a "^\[setup\]\|^\[chips\]" $OUT/$2.log | cut -c 1-200)
}
run change warm internlm2-serve-chat 2100000001 0
for S in 2200000011 2300000017 2400000019 2500000021; do
  run parent p_${S}_a internlm2-serve-chat $S 0
  run change c_${S}_a internlm2-serve-chat $S 0
  run change c_${S}_b internlm2-serve-chat $S 0
  run parent p_${S}_b internlm2-serve-chat $S 0
done
run parent p_traced internlm2-serve-chat 2600000023 1
run change c_traced internlm2-serve-chat 2600000023 1
run parent jp_a jamba2-serve-chat-burst 2700000027 0
run change jc_a jamba2-serve-chat-burst 2700000027 0
run change jc_traced jamba2-serve-chat-burst 2800000029 1
run parent jp_traced jamba2-serve-chat-burst 2800000029 1
# (ii) a foreign process holds the chip while a run starts: this tree waits, the parent's exits 1
hold() { python3 ci/chip_calls/pr45/hold_chip.py > $OUT/holder_$1.log 2>&1 & H=$!
  for i in $(seq 120); do grep -q READY $OUT/holder_$1.log && break; sleep 0.5; done; echo "holder $H up $(date +%T)"; }
hold change
( sleep 25; kill -9 $H; echo "holder killed $(date +%T)" ) &
run change ii_change_held internlm2-serve-chat 2900000031 0
wait
run change ii_change_next internlm2-serve-chat 2900000033 0
hold parent
run parent ii_parent_held internlm2-serve-chat 2900000031 0
tail -5 $OUT/ii_parent_held.log | cut -c 1-400
kill -9 $H; wait
