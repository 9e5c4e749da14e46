#!/bin/bash
# PR 63, call 5, from the COMMITTED files alone (_check/final63 = `git archive
# $(git write-tree)`): the cell as the driver will measure it, BENCHMARK.json
# with its entries appended: six seeds at the file's own rate, then a traced run.
mkdir -p chiprun_out/pr63
ln -sfn "$PWD/chiprun_out" _check/final63/chiprun_out
cd _check/final63
bash perfbench/tools/pr63/cell.sh final 0 -- 2147480401 2147480402 2147480403 2147480404 2147480405 2147480406
LINE_CHARS=9000 bash perfbench/tools/pr63/cell.sh final_traced 1 -- 2147480411
