# PR 54 call 1 (four chips): the step alone with `w_down`'s ring product not pinned (parent) and pinned (change), two of them traced and
# reduced by pr38/exposed.py, and loss + gradients of two layers compared bit for bit on the chip; then the cell from _check/parent
# (git archive 4ba736f) and _check/change (git archive $(git write-tree)): untraced parent, change, change, parent (the first of a tree
# compiles cold), then a traced pair.
OUT=/root/repo/chiprun_out/pr54/call1; mkdir -p $OUT
python3 ci/chip_calls/pr54/step_forms.py --forms parent,change,change,parent --steps 12 --trace parent,change --same-bits --out $OUT > $OUT/forms.log 2>&1
grep -a '^{' $OUT/forms.log | cut -c 1-1800; grep -a "Error\|error" $OUT/forms.log | tail -5
run() { # tree label seed trace
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload mistral7b-train-4chip --seed $3 --seconds 51 --trace $4 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   cp .perfbench_out/mistral7b-train-4chip/last_run.json $OUT/last_run_$2.json 2>/dev/null
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-620; grep -a "^\[setup\]\|^\[chips\]" $OUT/$2.log | cut -c 1-200)
}
run parent p1 5400000011 0
run change c1 5400000011 0
run change c2 5400000023 0
run parent p2 5400000023 0
run parent p_traced 5400000037 1
run change c_traced 5400000037 1
for f in p_traced c_traced; do grep -a "^{" $OUT/$f.log | tail -1 > $OUT/line_$f.json; done
