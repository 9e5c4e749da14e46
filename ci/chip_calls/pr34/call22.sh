set -x
mkdir -p chiprun_out/pangu
W=openpangu-serve-longctx
python3 perfbench/run.py --workload $W --seed 3000000011 --seconds 51 --trace 1 --override rate_per_s=1.5 > chiprun_out/pangu/traced_1.5.log 2>&1; echo rc=$?
grep -a "correct\]\|setup\]\|^{" chiprun_out/pangu/traced_1.5.log | cut -c 1-6000
cp .perfbench_out/$W/last_run.json chiprun_out/pangu/last_run_traced.json 2>/dev/null
for r in 1.0 2.0 2.5 3.0; do
  python3 perfbench/run.py --workload $W --seed 43$(echo $r | tr -d .) --seconds 30 --trace 0 --override rate_per_s=$r --override check_answers=1 > chiprun_out/pangu/sweep2_$r.log 2>&1; echo rc=$?
  grep -a "setup\]\|after\]\|^{" chiprun_out/pangu/sweep2_$r.log | cut -c 1-3000
done
