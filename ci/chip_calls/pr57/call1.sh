# PR 57 call 1 (four chips): the step alone with a layer's weight-gradient rings not ordered (parent: `fsdp.weight_grads` never handed
# `taken`) and ordered (change), in one process, two of them traced and reduced by pr38/exposed.py, and loss + gradients of two layers
# at the cell's widths compared bit for bit on the chip.
OUT=/root/repo/chiprun_out/pr57/call1; mkdir -p $OUT
python3 ci/chip_calls/pr57/step_forms.py --forms parent,change,change,parent --steps 12 --trace parent,change --same-bits --out $OUT > $OUT/forms.log 2>&1
grep -a '^{' $OUT/forms.log | cut -c 1-1800; grep -a "Error\|error" $OUT/forms.log | tail -5
