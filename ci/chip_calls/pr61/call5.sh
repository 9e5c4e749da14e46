# PR 61 call 5 (four chips): the tree of call 3 again (call 4's tree, the gather spread over the scan, read 0.8 ms a step slower), beside
# two of its parts switched off one at a time: `fused` (the kept half of the head's gradient fused with the sum, `weight_grads` without
# `alone`) and `loss_free` (no `fsdp.backward_behind`), each timed twice, one traced.
OUT=/root/repo/chiprun_out/pr61/call5; mkdir -p $OUT
python3 ci/chip_calls/pr61/step_forms.py --forms parent,change,fused,loss_free,change,fused,loss_free --steps 12 --trace fused,loss_free --out $OUT > $OUT/forms.log 2>&1
grep -a '^{' $OUT/forms.log | cut -c 1-1500; grep -a "Error\|error" $OUT/forms.log | tail -5
