OUT=/root/repo/chiprun_out/pr35; mkdir -p $OUT
W=internlm2-serve-chat
run() { # tree seed trace tag
  (cd _check/$1 && timeout 600 python3 perfbench/run.py --workload $W --seed $2 --seconds 51 --trace $3 > $OUT/$4.log 2>&1; echo rc=$? $4)
  grep -a "^{" $OUT/$4.log | tail -1 | cut -c 1-2500
  [ "$3" = 1 ] && cp _check/$1/.perfbench_out/$W/last_run.json $OUT/last_run_$4.json
}
for seed in 2147483999 3050607011 912345677; do
  run parent $seed 0 p_${seed}_a
  run change $seed 0 c_${seed}_a
  run change $seed 0 c_${seed}_b
  run parent $seed 0 p_${seed}_b
done
run change 4242424243 1 c_traced
run parent 4242424243 1 p_traced
