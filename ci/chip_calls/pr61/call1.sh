# PR 61 call 1 (four chips): the step alone with the partitioner's head (parent) and with the head's product carrying its exchanges
# (change: `tp.gather_matmul_alone`, the arrived shard kept for the backward), in one process, both traced and reduced by
# pr38/exposed.py, then loss + every gradient leaf of two layers at the cell's widths, one form beside the other on the chip.
OUT=/root/repo/chiprun_out/pr61/call1; mkdir -p $OUT
python3 ci/chip_calls/pr61/step_forms.py --forms parent,change,change,parent --steps 12 --trace parent,change --close --out $OUT > $OUT/forms.log 2>&1
grep -a '^{' $OUT/forms.log | cut -c 1-2200; grep -a "Error\|error" $OUT/forms.log | tail -5
