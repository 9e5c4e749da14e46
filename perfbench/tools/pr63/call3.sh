#!/bin/bash
# PR 63, call 3: (a) call 2's ten traced seconds were not written either, with
# the profiler's Python tracer off: three traced seconds, then five that begin
# before the window opens (the period's first request is due at 0.0 under
# every seed, so its prompt pass lies whole inside) or, if three fail too, 1.5;
# (b) calls 1-2 delivered 0.96-0.998 of offered with no queue (answers last
# 17-30 s, longer than the 20 s of warm traffic, so what streams into the
# window is not what streams out): the sweep again with warm_s 45.
export BENCH_ROOT=_check/sthink
o="--override rate_per_s=0.6 --override warm_s=45"
LINE_CHARS=9000 bash perfbench/tools/pr63/cell.sh t3 1 $o --override trace_window_s=[3.0,6.0] -- 2147483661
if grep -q '^{' chiprun_out/pr63/t3_s2147483661.out; then w="[-0.5,4.5]"; else w="[3.0,4.5]"; fi
LINE_CHARS=9000 bash perfbench/tools/pr63/cell.sh t5 1 $o --override trace_window_s=$w -- 2147483662
for point in 0.7:2147480321 0.8:2147480322 0.9:2147480323; do
  rate=${point%%:*}; seed=${point##*:}
  bash perfbench/tools/pr63/cell.sh w45_r$rate 0 --override rate_per_s=$rate --override warm_s=45 -- $seed
done
