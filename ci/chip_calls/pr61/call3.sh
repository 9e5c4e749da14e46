# PR 61 call 3 (four chips): as call 2, on the tree whose head's product is ONE over the whole sequence (`tp.gather_matmul_alone`), its
# dx products tied behind the gradient's half for the neighbour and the kept half's product kept out of the sum (`fsdp.weight_grads`, `alone`).
OUT=/root/repo/chiprun_out/pr61/call3; mkdir -p $OUT
python3 ci/chip_calls/pr61/step_forms.py --forms parent,change,change,parent --steps 12 --trace parent,change --close --out $OUT > $OUT/forms.log 2>&1
grep -a '^{' $OUT/forms.log | cut -c 1-2200; grep -a "Error\|error" $OUT/forms.log | tail -5
