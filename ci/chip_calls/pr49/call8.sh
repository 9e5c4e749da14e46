#!/bin/bash
# call 8: a second set of six seeds of the new cell, run FROM _check/change
# (`git archive $(git write-tree)`): the committed files are enough.
out=$PWD/chiprun_out/pr49/seeds2
mkdir -p $out
cd _check/change
for seed in $SEEDS; do
  python3 perfbench/run.py --workload granite4h-serve-ragsessions --seed $seed --seconds 51 --trace 0 > $out/seed_$seed.log 2>&1
  echo "seed $seed rc=$?"
  tail -1 $out/seed_$seed.log > $out/seed_$seed.line
  cp .perfbench_out/granite4h-serve-ragsessions/last_run.json $out/seed_$seed.json
  python3 ci/chip_calls/pr49/point.py $out/seed_$seed.json $out/seed_$seed.line
  grep -a "\[setup\]" $out/seed_$seed.log | cut -c1-300
  grep -a "\[failed\]\|Traceback\|RESOURCE\|NOT OK" $out/seed_$seed.log | head -3
done
