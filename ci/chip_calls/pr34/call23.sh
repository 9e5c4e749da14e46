set -x
mkdir -p chiprun_out/pangu
W=openpangu-serve-longctx
for seed in 2147483659 77 900000001; do
  python3 perfbench/run.py --workload $W --seed $seed --seconds 30 --trace 0 --override rate_per_s=1.5 --control int8 > chiprun_out/pangu/control_$seed.log 2>&1; echo rc=$?
  grep -a "correct\] [a-z]\|setup\]\|^{" chiprun_out/pangu/control_$seed.log | cut -c 1-1500
done
run() { # tree cell seed trace tag
  (cd $1 && python3 perfbench/run.py --workload $2 --seed $3 --seconds 51 --trace $4 > /root/repo/chiprun_out/pangu/pair_$5.log 2>&1; echo rc=$?)
  grep -a "correct\] [a-z]\|setup\]\|^{" chiprun_out/pangu/pair_$5.log | cut -c 1-4000
}
run _check/parent kimi-linear-serve-longgen 5151 0 kimi_parent
run . kimi-linear-serve-longgen 5151 0 kimi_change
run . internlm2-serve-chat 6161 0 chat_change
run _check/parent internlm2-serve-chat 6161 0 chat_parent
run . kimi-linear-serve-longgen 5252 1 kimi_change_traced
run _check/parent kimi-linear-serve-longgen 5252 1 kimi_parent_traced
