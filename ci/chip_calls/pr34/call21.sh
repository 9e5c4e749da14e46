set -x
mkdir -p chiprun_out/pangu
W=openpangu-serve-longctx
python3 perfbench/run.py --workload $W --seed 3000000011 --seconds 51 --trace 1 > chiprun_out/pangu/first_traced.log 2>&1; echo rc=$?
tail -c 6000 chiprun_out/pangu/first_traced.log
for r in 1.5 2.0 2.5 3.0 3.5; do
  python3 perfbench/run.py --workload $W --seed 41$(echo $r | tr -d .) --seconds 30 --trace 0 --override rate_per_s=$r --override check_answers=1 > chiprun_out/pangu/sweep_$r.log 2>&1; echo rc=$?
  tail -n 12 chiprun_out/pangu/sweep_$r.log | cut -c 1-2500
done
cp .perfbench_out/$W/last_run.json chiprun_out/pangu/last_run_sweep.json 2>/dev/null
