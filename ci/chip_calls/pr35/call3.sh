OUT=/root/repo/chiprun_out/pr35; mkdir -p $OUT
W=internlm2-serve-chat
run() { # tree seed trace tag
  (cd _check/$1 && timeout 600 python3 perfbench/run.py --workload $W --seed $2 --seconds 51 --trace $3 > $OUT/$4.log 2>&1; echo rc=$? $4)
  grep -a "^{" $OUT/$4.log | tail -1 | cut -c 1-600
}
run final 1000000007 0 f_warm   # the machine's first run: compiles, and reads what a cold host reads
run final 2999111333 0 f_2999111333
run parent 2999111333 0 q_2999111333
run parent 77001122 0 q_77001122
run final 77001122 0 f_77001122
run final 3456700021 0 f_3456700021
run parent 3456700021 0 q_3456700021
run parent 1234500077 0 q_1234500077
run final 1234500077 0 f_1234500077
(cd _check/final && timeout 900 python3 chip_smoke.py > $OUT/smoke.log 2>&1; echo rc=$? smoke; tail -1 $OUT/smoke.log | cut -c 1-1500)
