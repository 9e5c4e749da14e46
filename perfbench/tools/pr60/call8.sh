#!/bin/bash
# PR 60, call 8 (after the review), from the COMMITTED files alone (`git archive
# $(git write-tree)` in _check/rev): the replay's decode at the timed step's own
# attention length, the prompt kernel's events put on the wall clock: a traced
# run, two sound seeds, both controls; then the knee again with 51 s windows.
mkdir -p chiprun_out/pr60
ln -sfn "$PWD/chiprun_out" _check/rev/chiprun_out
cd _check/rev
bash perfbench/tools/pr60/cell.sh r_traced 1 -- 2147480811
bash perfbench/tools/pr60/cell.sh r_sound 0 -- 2147480801 2147480802
bash perfbench/tools/pr60/cell.sh r_int8 0 --control int8 -- 2147480821
bash perfbench/tools/pr60/cell.sh r_no_window 0 --control no_window -- 2147480831
bash perfbench/tools/pr60/sweep.sh knee51 51 1.6:2147480841 1.8:2147480842 2.0:2147480843
