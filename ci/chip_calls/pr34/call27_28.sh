set -x
W=openpangu-serve-longctx; mkdir -p chiprun_out/pangu
i=0
for seed in 2147483777 3111222333 17 4000000007 1234567891 2999999999; do
  i=$((i+1))
  python3 perfbench/run.py --workload $W --seed $seed --seconds 51 --trace 0 > chiprun_out/pangu/last_$i.log 2>&1; echo rc=$?
done
