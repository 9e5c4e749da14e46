#!/bin/bash
# PR 63, call 9, the final tree after the review, from the COMMITTED files
# alone (_check/final63 = `git archive $(git write-tree)`; _check/parent63 =
# `git archive` of the parent commit with this PR's BENCHMARK.json and
# perfbench/ laid over it, as the driver lays them): (a) the parent's checkout
# refuses the new cell in seconds; (b) the cell traced twice (the result line
# must hold the eight `sthink` metrics AND Command A+'s two readers whose lists
# the cell joined) and two more seeds; (c) the Command A+ cell traced on the
# parent's checkout with the overlay (its readers read what they read; the
# new ones nothing).
mkdir -p chiprun_out/pr63
for d in _check/final63 _check/parent63; do ln -sfn "$PWD/chiprun_out" $d/chiprun_out; done
t0=$(date +%s.%N)
(cd _check/parent63 && timeout 120 python3 perfbench/run.py --workload smallthinker-serve-longanswer \
   --seed 2147480601 --seconds 51 --trace 0 > chiprun_out/pr63/parent_refuses2.out 2>&1; echo "parent rc $?")
t1=$(date +%s.%N)
echo "the parent's checkout answered in $(python3 -c "print(round($t1 - $t0, 2))") s:"
tail -n 3 chiprun_out/pr63/parent_refuses2.out
(cd _check/final63
 LINE_CHARS=9000 bash perfbench/tools/pr63/cell.sh end_traced 1 -- 2147480611 2147480612
 bash perfbench/tools/pr63/cell.sh end 0 -- 2147480613 2147480614)
(cd _check/parent63 && python3 perfbench/run.py --workload command-a-plus-serve-mixedqueue --seed 2147480621 \
   --seconds 51 --trace 1 > chiprun_out/pr63/cmda_parent_traced2.out 2> chiprun_out/pr63/cmda_parent_traced2.err
 echo "cmda_parent_traced2 rc $?"; grep -E "^\[correct\]" chiprun_out/pr63/cmda_parent_traced2.out | tail -6
 tail -n 1 chiprun_out/pr63/cmda_parent_traced2.out | cut -c1-6000)
