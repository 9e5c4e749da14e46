set -x
OUT=/root/repo/chiprun_out/s2; mkdir -p $OUT
W=openpangu-serve-longctx
cd _check/final34
run() { # cell seed trace tag
  timeout 900 python3 perfbench/run.py --workload $1 --seed $2 --seconds 51 --trace $3 > $OUT/$4.log 2>&1; echo rc=$? $4
}
run $W 2147483777 0 pangu_s2147483777
run $W 3111222333 0 pangu_s3111222333
run $W 2999000111 0 pangu_s2999000111
run $W 17 1 pangu_s17_traced
cp .perfbench_out/$W/last_run.json $OUT/last_run_pangu_traced.json
run kimi-linear-serve-longgen 5151 0 kimi_change_s5151
run kimi-linear-serve-longgen 5252 1 kimi_change_s5252_traced
cp .perfbench_out/kimi-linear-serve-longgen/last_run.json $OUT/last_run_kimi_traced.json
