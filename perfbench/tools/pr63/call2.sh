#!/bin/bash
# PR 63, call 2: call 1's traced run never wrote its ten seconds of trace (the
# profiler's Python tracer: `lib/sthink_replica.trace_between` now turns it
# off) and its 51 s window at 1.0 req/s delivered 0.959 of offered: a traced
# run at 0.6/s, then the sweep below 1.0 by 51 s windows, one seed a point.
export BENCH_ROOT=_check/sthink
LINE_CHARS=9000 bash perfbench/tools/pr63/cell.sh second_traced 1 --override rate_per_s=0.6 -- 2147483660
bash perfbench/tools/pr63/sweep.sh knee 51 0.7:2147480311 0.8:2147480312 0.9:2147480313
