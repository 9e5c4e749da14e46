set -x
mkdir -p chiprun_out/pangu
W=openpangu-serve-longctx
i=0
for seed in 2147483777 3111222333 17 4000000007 1234567891 2999999999; do
  i=$((i+1))
  python3 perfbench/run.py --workload $W --seed $seed --seconds 51 --trace 0 > chiprun_out/pangu/cell_$i.log 2>&1; echo rc=$?
done
python3 perfbench/run.py --workload $W --seed 3456789012 --seconds 51 --trace 1 > chiprun_out/pangu/cell_traced.log 2>&1; echo rc=$?
cp .perfbench_out/$W/last_run.json chiprun_out/pangu/last_run_cell_traced.json
python3 perfbench/run.py --workload internlm2-serve-chat --seed 99 --seconds 51 --trace 1 > chiprun_out/pangu/chat_traced.log 2>&1; echo rc=$?
