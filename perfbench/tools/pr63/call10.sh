#!/bin/bash
# PR 63, call 10, the final archive (_check/final63 = `git archive $(git
# write-tree)` after `SthinkBenchReplica.trace_stop` took its longer wait):
# one traced run of the cell, the first run of its machine.
mkdir -p chiprun_out/pr63
ln -sfn "$PWD/chiprun_out" _check/final63/chiprun_out
cd _check/final63
LINE_CHARS=9000 bash perfbench/tools/pr63/cell.sh final2_traced 1 -- 2147480711
