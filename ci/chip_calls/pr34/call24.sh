set -x
mkdir -p chiprun_out/pangu
W=openpangu-serve-longctx
for r in 1.2 1.1 1.3 1.4; do
  python3 perfbench/run.py --workload $W --seed 47$(echo $r | tr -d .) --seconds 30 --trace 0 --override rate_per_s=$r --override check_answers=1 > chiprun_out/pangu/sweep3_$r.log 2>&1; echo rc=$?
  python3 - <<PY
import json
rec=json.load(open('.perfbench_out/$W/last_run.json'))
reqs=rec['replica']['requests']
w=sorted((r['admit']-r['submit'])*1e3 for r in reqs if 'admit' in r)
win=rec['window_rows']
json.dump({'rate': $r, 'denied': sum('denied' in r for r in reqs), 'n': len(reqs),
  'admit_wait_ms_p50': w[len(w)//2], 'admit_wait_ms_p95': w[int(.95*len(w))],
  'offered_tokens': sum(r['max_new_tokens'] for r in win), 'drained_s': rec['drained_s']}, open('chiprun_out/pangu/sweep3_$r.json','w'))
PY
done
