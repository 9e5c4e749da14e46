set -x
OUT=/root/repo/chiprun_out/s2; mkdir -p $OUT
cd _check/final34
timeout 400 python3 perfbench/run.py --workload kimi-linear-serve-longgen --seed 5151 --seconds 51 --trace 0 > $OUT/kimi_change_s5151_again.log 2>&1; echo rc=$?
grep -a "setup\]\|after\]\|^{" $OUT/kimi_change_s5151_again.log | cut -c1-600
