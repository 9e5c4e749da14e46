#!/bin/bash
# call 6: the tree as git would commit it (_check/final = git archive $(git write-tree)): the cell traced and untraced;
# the int8 control on three seeds; the parent with this PR's benchmark laid over it: the new cell, and one old cell traced
mkdir -p chiprun_out/pr39
OUT=$PWD/chiprun_out/pr39
cd _check/final
python3 perfbench/run.py --workload evabyte-serve-longdoc --seed 2147495039 --seconds 51 --trace 1 > $OUT/final_traced.log 2>&1; echo "final traced rc=$?"
cp .perfbench_out/evabyte-serve-longdoc/last_run.json $OUT/final_traced.json
python3 perfbench/run.py --workload evabyte-serve-longdoc --seed 2147496043 --seconds 51 --trace 0 > $OUT/final_untraced.log 2>&1; echo "final untraced rc=$?"
for S in 2147497049 2147498051 2147499053; do
  python3 perfbench/run.py --workload evabyte-serve-longdoc --seed $S --seconds 51 --trace 0 --control int8 > $OUT/control_$S.log 2>&1; echo "control $S rc=$?"
  grep -E "^\[correct\] [a-z_]+ =|^\[correct\] the" $OUT/control_$S.log | cut -c1-200
done
cd ../parent
timeout 120 python3 perfbench/run.py --workload evabyte-serve-longdoc --seed 2147500057 --seconds 51 --trace 0 > $OUT/parent_newcell.log 2>&1; echo "parent on the new cell rc=$?"
cat $OUT/parent_newcell.log | tail -3
python3 perfbench/run.py --workload jamba2-serve-chat-burst --seed 2147501059 --seconds 51 --trace 1 > $OUT/parent_jamba_traced.log 2>&1; echo "parent jamba traced rc=$?"
tail -1 $OUT/parent_jamba_traced.log | cut -c1-1500
cd ../final
grep -E "^\[setup\]|^\{" $OUT/final_traced.log | cut -c1-6000
grep -E "^\[setup\]|^\{" $OUT/final_untraced.log | cut -c1-1500
