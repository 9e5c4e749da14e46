# PR 64 call 4 (one chip): `kimi-linear-serve-longgen`, parent (`_check/parent` = git archive b683862) and change on ONE cache
# directory (the machine's): the parent first (cold if it must: it warms the cache for both trees), the change's first life
# (no list yet; traced: its misses on the cache the parent warmed), then pairs at a seed a pair, the last of the change traced.
ROOT=$PWD; OUT=$ROOT/chiprun_out/pr64/${CALL:-call4}; mkdir -p $OUT
CELL=${CELL:-kimi-linear-serve-longgen}
run() { # label tree seed trace
  local dir=$ROOT; [ "$2" = parent ] && dir=$ROOT/_check/parent
  ( cd $dir && timeout 1500 python3 $ROOT/ci/chip_calls/pr64/ahead.py --workload $CELL --seed $3 --seconds 51 --trace $4 > $OUT/$1.log 2>&1 ); echo "rc=$? $1 $(date +%T)"
  grep -a "^{" $OUT/$1.log | tail -1 > $OUT/line_$1.json; python3 ci/chip_calls/pr58/brief.py $OUT/line_$1.json
  grep -a "^\[ahead\]\|^\[programs\]\|^\[setup_spans\] [a-zL0-9]" $OUT/$1.log | cut -c 1-1500
}
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}"; ls ${JAX_COMPILATION_CACHE_DIR:-.jax_compile_cache}-programs 2>/dev/null
run parent_first parent 6400000007 0
run change_life1 change 6400000011 1
run change_1 change 6400000013 0
run parent_1 parent 6400000013 0
run parent_2 parent 6400000017 0
run change_2 change 6400000017 0
run change_3t change 6400000019 1
ls -l ${JAX_COMPILATION_CACHE_DIR:-.jax_compile_cache}-programs; cat ${JAX_COMPILATION_CACHE_DIR:-.jax_compile_cache}-programs/* | cut -c 1-150
