#!/bin/bash
# PR 63, call 8, after the review. The traffic file says warm_s 20, as ISSUE 63
# does. (a) the knee again: a third and a fourth 51 s window at 1.0 and at 0.9
# req/s (warm_s 45 laid over the file for the sweep alone, so that a window
# delivers what it is offered where no queue grows); the knee is the highest
# rate at which EVERY window, calls 3-4's two included, read 0.99 or more, and
# the rate 0.65 x knee rounded down to 0.1; (b) six seeds at that rate with the
# file's own warm_s 20; if they spread over 0.5% with the farthest left out,
# ISSUE 63's remedy: six more a tenth of a request a second higher; (c) two
# faults of the block planted in the serving program, for an upper reading of
# token_gap_mean_spacings: the router reads the FFN's input, SwiGLU experts.
cell=smallthinker-serve-longanswer
mkdir -p chiprun_out/pr63
for point in 1.0:2147480511 1.0:2147480512 0.9:2147480513 0.9:2147480514; do
  rate=${point%%:*}; seed=${point##*:}
  out=chiprun_out/pr63/knee3_r${rate}_s${seed}
  python3 perfbench/run.py --workload $cell --seed $seed --seconds 51 --trace 0 \
    --override rate_per_s=$rate --override warm_s=45 > $out.out 2> $out.err
  echo "rate $rate seed $seed rc $?"
  cp .perfbench_out/$cell/last_run.json $out.last_run.json
  python3 perfbench/tools/pr63/readings.py $out.out
done
rate=$(python3 - <<'PY'
import glob, json, subprocess
shares = {"1.0": [1.014, 0.9902], "0.9": [0.9925, 0.9931]}   # calls 3-4
for f in sorted(glob.glob("chiprun_out/pr63/knee3_*.out")):
    got = subprocess.run(["python3", "perfbench/tools/pr63/readings.py", f],
                         capture_output=True, text=True).stdout
    if not got.startswith("{"):
        continue     # a run without a result line: the rule reads the others
    d = json.loads(got)
    if "offered" not in d:
        continue
    shares[f.split("_r")[1].split("_s")[0]].append(d["tokens_per_s"] / d["offered"])
knee = 1.0 if min(shares["1.0"]) >= 0.99 else 0.9 if min(shares["0.9"]) >= 0.99 else 0.8
import math
print(math.floor(0.65 * knee * 10 + 1e-9) / 10)
PY
)
file_rate=$(python3 -c "import json; print(json.load(open('perfbench/traffic/context-longanswer-open-loop.json'))['rate_per_s'])")
echo "KNEE RULE gives the rate $rate; the file's is $file_rate"
seeds() {   # tag rate seed ...
  tag=$1; r=$2; shift 2
  over=(--override rate_per_s=$r)
  [ "$r" = "$file_rate" ] && over=()     # the cell as the file has it
  bash perfbench/tools/pr63/cell.sh $tag 0 "${over[@]}" -- "$@"
  python3 perfbench/tools/pr63/spread.py "chiprun_out/pr63/${tag}_s*.out" | tee chiprun_out/pr63/$tag.spread
}
seeds warm20 $rate 2147480521 2147480522 2147480523 2147480524 2147480525 2147480526
left_out=$(tail -n 1 chiprun_out/pr63/warm20.spread | cut -d' ' -f3)
if python3 -c "import sys; sys.exit(0 if float('$left_out') > 0.5 else 1)"; then
  higher=$(python3 -c "print(round($rate + 0.1, 1))")
  echo "spread $left_out% > 0.5%: six more at $higher"
  seeds warm20hi $higher 2147480531 2147480532 2147480533 2147480534 2147480535 2147480536
fi
over=(--override rate_per_s=$rate)
[ "$rate" = "$file_rate" ] && over=()
bash perfbench/tools/pr63/cell.sh late_route 0 --control late_route "${over[@]}" -- 2147480541
bash perfbench/tools/pr63/cell.sh silu_gate 0 --control silu_gate "${over[@]}" -- 2147480542
