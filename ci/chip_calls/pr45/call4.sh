# PR 45 call 4 (one chip): three spellings of the walk's operands beside the grid: x_v1 (current rows [B, kvh, 1, hd] bf16, q and the
# output padded to a tile a kv head), x_v2 (current rows [B, kvh, hd] float32), walk256 (this tree: v2 + q and the output 16 rows a slot)
OUT=/root/repo/chiprun_out/pr45/call4; mkdir -p $OUT
python3 ci/chip_calls/pr45/step_forms.py --alone --forms grid,x_v1,x_v2,walk256 --out $OUT > $OUT/alone.log 2>&1; grep -a "us/call\|Error\|error" $OUT/alone.log | tail -70
python3 ci/chip_calls/pr45/step_forms.py --forms grid,x_v1,x_v2,walk256 --buckets 64,256,512,1024 --fills 4:330,32:330,32:1023 --out $OUT > $OUT/step_forms.log 2>&1; grep -a "step ms\|Error\|error" $OUT/step_forms.log | tail -60
