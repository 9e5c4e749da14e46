#!/bin/bash
# PR 63, call 4, warm_s 45 in the traffic file (call 3: with it a 51 s window
# delivers what it is offered, 1.0000 and 1.0003 at 0.6 req/s, 0.9972 at 0.7,
# 0.9957 at 0.8, 0.9925 at 0.9): (a) the knee: two windows at 1.0, one at 1.1,
# a second at 0.9; (b) the int8 control at the provisional rate, three seeds.
export BENCH_ROOT=_check/sthink
bash perfbench/tools/pr63/sweep.sh knee45 51 1.0:2147480331 1.0:2147480332 1.1:2147480333 0.9:2147480334
bash perfbench/tools/pr63/cell.sh int8 0 --control int8 -- 2147480341 2147480342 2147480343
