#!/bin/bash
# the committed files alone (`git archive $(git write-tree)` in _check/final): one run of the new cell
mkdir -p chiprun_out/pr60
(cd _check/final && python3 perfbench/run.py --workload command-a-plus-serve-mixedqueue --seed 2147480701 --seconds 51 --trace 0) \
  > chiprun_out/pr60/committed_s2147480701.out 2> chiprun_out/pr60/committed_s2147480701.err
echo "committed files rc $?: $(tail -n 1 chiprun_out/pr60/committed_s2147480701.out | cut -c1-300)"
