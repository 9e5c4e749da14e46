#!/bin/bash
# call 6 (one chip) / call 7 (four chips, CELLS=mistral7b-train-4chip): every
# other cell once, parent against change, untraced, the same seed on both
# sides: none may move by more than its bound.
mkdir -p chiprun_out/pr49/others
for cell in $CELLS; do
  seed=$((2147484000 + RANDOM % 1000))
  for side in parent change; do
    log=$PWD/chiprun_out/pr49/others/${cell}_$side.log
    (cd _check/$side && python3 perfbench/run.py --workload $cell --seed $seed --seconds 51 --trace 0) > $log 2>&1
    echo "$cell $side seed $seed rc=$? $(tail -1 $log | cut -c1-420)"
  done
done
