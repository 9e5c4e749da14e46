#!/bin/bash
# PR 63, call 7, from the COMMITTED files alone (_check/final63 = `git archive
# $(git write-tree)` of the final tree): the traffic file now traces FOUR
# seconds (call 5's five were written 104 s after they ended, of ~170 s the
# run allows): two traced runs, then two more seeds untraced.
mkdir -p chiprun_out/pr63
ln -sfn "$PWD/chiprun_out" _check/final63/chiprun_out
cd _check/final63
LINE_CHARS=9000 bash perfbench/tools/pr63/cell.sh last_traced 1 -- 2147480431 2147480432
bash perfbench/tools/pr63/cell.sh last 0 -- 2147480433 2147480434
