# PR 46 call 4 (four chips): each rank's own copy of a norm scale read by a reshape of x (no manual region: `_check/alt`, this tree) beside
# the regions of calls 1 to 3 (`_check/final`) and the parent: the step alone in three forms, the step's tracing / lowering / cache read
# one fresh process a line, then the cell untraced alt (cold), parent, final, alt, and alt traced.
OUT=/root/repo/chiprun_out/pr46/call4; mkdir -p $OUT
python3 ci/chip_calls/pr46/step_forms.py --forms parent,change,regions,change --steps 12 --trace change --out $OUT > $OUT/forms.log 2>&1
grep -a '^{' $OUT/forms.log | cut -c 1-1500; grep -a "Error\|error" $OUT/forms.log | tail -5
for t in alt parent final alt parent; do
  timeout 600 python3 ci/chip_calls/pr46/compile_split.py _check/$t 2>$OUT/split_$t.err | grep -a "^{" | tee -a $OUT/compile_split.jsonl
done
run() { # tree label seed trace
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload mistral7b-train-4chip --seed $3 --seconds 51 --trace $4 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   cp .perfbench_out/mistral7b-train-4chip/last_run.json $OUT/last_run_$2.json 2>/dev/null
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-620; grep -a "^\[setup\]\|^\[chips\]" $OUT/$2.log | cut -c 1-200)
}
run alt a5 4660000061 0
run parent p5 4660000061 0
run final f5 4670000071 0
run alt a6 4670000071 0
run alt a_traced 4680000083 1
