#!/bin/bash
# call 5: the claimed cell once from `_check/change` (`make_change.sh`: what
# git would commit), cold cache of its own; then a cell that runs none of the
# changed code but imports the changed module, `jamba2-serve-chat-burst`,
# parent against change, one pair, each side's cache its own (cold).
mkdir -p chiprun_out/pr50
one() {  # dir, side, cell, seed
  log=$PWD/chiprun_out/pr50/$3_$2.log
  (cd $1 && JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_compile_cache_call/$2 \
    python3 perfbench/run.py --workload $3 --seed $4 --seconds 51 --trace 0) > $log 2>&1
  echo "$3 $2 seed=$4 rc=$? $(python3 ci/chip_calls/pr50/point.py $1/.perfbench_out/$3/last_run.json $log 2>&1 | tail -1)"
  grep -a "NOT OK\|Traceback\|RESOURCE\|perfbench:\|\[chips\] waited" $log | cut -c1-300 | head -5
}
one _check/change archive kimi-linear-serve-longgen $((2147483000 + RANDOM))
seed=$((2147483000 + RANDOM))
one _check/parent parent jamba2-serve-chat-burst $seed
one _check/change archive jamba2-serve-chat-burst $seed
