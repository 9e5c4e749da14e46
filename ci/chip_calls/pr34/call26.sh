set -x
mkdir -p chiprun_out/pangu
W=openpangu-serve-longctx
cd _check/final34
i=0
for seed in 2147483777 3111222333 17 4000000007 1234567891 2999999999; do
  i=$((i+1))
  python3 perfbench/run.py --workload $W --seed $seed --seconds 51 --trace 0 > /root/repo/chiprun_out/pangu/final_$i.log 2>&1; echo rc=$?
done
python3 perfbench/run.py --workload $W --seed 3456789012 --seconds 51 --trace 1 > /root/repo/chiprun_out/pangu/final_traced.log 2>&1; echo rc=$?
cp .perfbench_out/$W/last_run.json /root/repo/chiprun_out/pangu/last_run_final_traced.json
python3 chip_smoke.py --model pangu --seed 7 > /root/repo/chiprun_out/pangu/final_smoke.log 2>&1; echo rc=$?
python3 perfbench/run.py --workload kimi-linear-serve-longgen --seed 5353 --seconds 51 --trace 0 > /root/repo/chiprun_out/pangu/final_kimi.log 2>&1; echo rc=$?
