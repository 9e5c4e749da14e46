# PR 64 call 2 (one chip): why a background thread's read is slow, stage by stage, and the other division of the work (reads2.py).
ROOT=$PWD; OUT=$ROOT/chiprun_out/pr64/call2; mkdir -p $OUT
python3 ci/chip_calls/pr64/reads2.py > $OUT/reads2.log 2>&1; echo "rc=$?"
grep -a "^{\|Traceback\|Error" $OUT/reads2.log | cut -c 1-1800
