#!/bin/bash
# One cell, parent (`_check/parent`) against change (this tree). ORDER is the
# sides in turn, untraced; TRACED the same with `--trace 1`, after them. A pair
# shares a seed, every pair has its own. Each side keeps its compile cache in
# a directory of its own, made anew by this call: the machine's own
# (`JAX_COMPILATION_CACHE_DIR`, which comes with some 180 MiB of earlier calls'
# programs and holds no more) let each side's 18 big programs evict the
# other's in call 1, so that every second "warm" run compiled them again. The
# first run of a side is so its COLD one (all 57 programs missed), the rest
# warm. Then, with WARM=kimi|pangu, each side's replica constructor once more
# with its `xla.compile` spans (`warm_spans.py`), warm cache.
# CELL=... ORDER="parent change change parent" [TRACED="change parent"] [WARM=kimi]
mkdir -p chiprun_out/pr50
env | grep -a "^JAX_\|^XLA_\|^TPU_\|^LIBTPU" | cut -c1-200
n=0
seed=0
declare -A seen
run() {  # side, trace
  side=$1
  if [ $((n % 2)) -eq 0 ]; then seed=$((${SEED0:-2147483000} + RANDOM)); fi
  n=$((n + 1))
  dir=$([ $side = parent ] && echo _check/parent || echo .)
  log=$PWD/chiprun_out/pr50/${CELL}_${side}_$n.log
  state=$([ -z "${seen[$side]}" ] && echo cold || echo warm)
  seen[$side]=1
  (cd $dir && JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_compile_cache_call/$side \
    python3 perfbench/run.py --workload $CELL --seed $seed --seconds 51 --trace $2) > $log 2>&1
  rc=$?
  echo "$CELL $side $state trace=$2 seed=$seed rc=$rc $(python3 ci/chip_calls/pr50/point.py $dir/.perfbench_out/$CELL/last_run.json $log 2>&1 | tail -1)"
  grep -a "NOT OK\|Traceback\|RESOURCE\|perfbench:\|\[chips\] waited" $log | cut -c1-300 | head -5
}
for side in $ORDER; do run $side 0; done
for side in $TRACED; do run $side 1; done
if [ -n "$WARM" ]; then
  for side in parent change; do
    dir=$([ $side = parent ] && echo _check/parent || echo .)
    echo "=== $side: the replica's constructor, warm cache, its xla.compile spans"
    JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_compile_cache_call/$side \
      python3 ci/chip_calls/pr50/warm_spans.py $dir $WARM 2> chiprun_out/pr50/warm_spans_${WARM}_$side.err | tee chiprun_out/pr50/warm_spans_${WARM}_$side.jsonl | cut -c1-400
  done
fi
du -sh .jax_compile_cache_call/* 2>/dev/null
