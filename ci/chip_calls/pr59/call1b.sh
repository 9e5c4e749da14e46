# PR 59 call 1b (four chips): every ring staging (change) beside gate's and up's alone (gate_up) once more, the former traced: call 1
# read the two alike (294.32 | 294.33 ms) where five more staged slices a layer were expected to cost ~1 ms a step. (As run, the
# forms were named `every` and `change`: README.md.)
OUT=/root/repo/chiprun_out/pr59/call1b; mkdir -p $OUT
python3 ci/chip_calls/pr59/step_forms.py --forms change,gate_up,change,gate_up --steps 12 --trace change --out $OUT > $OUT/forms.log 2>&1
grep -a '^{' $OUT/forms.log | cut -c 1-1200; grep -a "Error\|error" $OUT/forms.log | tail -5
