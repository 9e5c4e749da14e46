# PR 45 call 1: the device nodes, the kernel alone, the step alone
OUT=/root/repo/chiprun_out/pr45; mkdir -p $OUT
python3 ci/chip_calls/pr45/dev_nodes.py --visible ";" > $OUT/dev_nodes.log 2>&1; tail -60 $OUT/dev_nodes.log | cut -c 1-400
python3 ci/chip_calls/pr45/step_forms.py --alone --out $OUT > $OUT/alone.log 2>&1; grep -a "us/call\|Error\|error" $OUT/alone.log | tail -70
python3 ci/chip_calls/pr45/step_forms.py --out $OUT > $OUT/step_forms.log 2>&1; grep -a "step ms\|Error\|error" $OUT/step_forms.log | tail -90
