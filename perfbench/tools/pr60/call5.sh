#!/bin/bash
# PR 60, call 5, on the final tree with the final limits: the parent on the new
# cell (fails at once), one old cell traced on the parent under this PR's
# benchmark files, the cells the change must not move (parent, change, change,
# parent), then the new cell: six seeds with the seam in the longest gap and a traced run.
bash perfbench/tools/pr60/others.sh mistral7b-train-1chip internlm2-serve-chat
(cd _check/parent && python3 perfbench/run.py --workload internlm2-serve-chat --seed 2147480431 --seconds 51 --trace 1) \
  > chiprun_out/pr60/parent_traced_internlm2.out 2> chiprun_out/pr60/parent_traced_internlm2.err
echo "parent, traced old cell rc $?: $(tail -n 1 chiprun_out/pr60/parent_traced_internlm2.out | cut -c1-300)"
bash perfbench/tools/pr60/cell.sh final 0 -- 2147480601 2147480602 2147480603 2147480604 2147480605 2147480606
bash perfbench/tools/pr60/cell.sh traced 1 -- 2147480611
cp .perfbench_out/command-a-plus-serve-mixedqueue/last_run.json chiprun_out/pr60/traced_s2147480611.last_run.json
