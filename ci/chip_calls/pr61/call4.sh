# PR 61 call 4 (four chips): as call 3, on the tree whose head's weights are gathered over fsdp in slices that ride the layers' forward
# scan (`fsdp.SpreadGather`: 96 rows of every shard a layer), the head's product ONE by the whole weight (`tp.gather_matmul_alone`).
OUT=/root/repo/chiprun_out/pr61/call4; mkdir -p $OUT
python3 ci/chip_calls/pr61/step_forms.py --forms parent,change,change,parent --steps 12 --trace parent,change --close --out $OUT > $OUT/forms.log 2>&1
grep -a '^{' $OUT/forms.log | cut -c 1-2200; grep -a "Error\|error" $OUT/forms.log | tail -5
