# PR 59 call 1 (four chips): the step alone with no kept product staged (parent), with gate's and up's alone reading a staged slice
# (gate_up) and with every ordered ring staging (change: the program as committed), in one process, two of them traced and reduced by
# pr38/exposed.py, and loss + gradients of two layers at the cell's widths compared bit for bit on the chip. (As run, the forms were
# named `change` and `every`: the tree then staged gate's and up's alone, by an argument parallel/tp.py set; README.md.)
OUT=/root/repo/chiprun_out/pr59/call1; mkdir -p $OUT
python3 ci/chip_calls/pr59/step_forms.py --forms parent,gate_up,change,gate_up,parent --steps 12 --trace parent,gate_up --same-bits --out $OUT > $OUT/forms.log 2>&1
grep -a '^{' $OUT/forms.log | cut -c 1-1800; grep -a "Error\|error" $OUT/forms.log | tail -5
