# PR 64 calls 5a / 5b / 6 (one chip): the other serving cells, each: the change's first life (cold where the machine's cache
# lost the cell's programs; it records the list), the change's second life (replays it), the parent (`_check/parent`) on the
# same warm cache. CELLS names the cells, CALL the directory the logs go to.
ROOT=$PWD; OUT=$ROOT/chiprun_out/pr64/${CALL:-call5}; mkdir -p $OUT
run() { # label tree cell seed trace
  local dir=$ROOT; [ "$2" = parent ] && dir=$ROOT/_check/parent
  ( cd $dir && timeout 1200 python3 $ROOT/ci/chip_calls/pr64/ahead.py --workload $3 --seed $4 --seconds 51 --trace $5 > $OUT/$1.log 2>&1 ); echo "rc=$? $1 $(date +%T)"
  grep -a "^{" $OUT/$1.log | tail -1 > $OUT/line_$1.json; python3 ci/chip_calls/pr58/brief.py $OUT/line_$1.json
  grep -a "^\[ahead\]\|^\[programs\]" $OUT/$1.log | cut -c 1-1200
}
seed=6400000100
for cell in $CELLS; do
  seed=$((seed + 7))
  run ${cell}_life1 change $cell $seed 0
  run ${cell}_life2 change $cell $((seed + 1)) 0
  run ${cell}_parent parent $cell $((seed + 1)) 0
done
ls -l ${JAX_COMPILATION_CACHE_DIR:-.jax_compile_cache}-programs ${JAX_COMPILATION_CACHE_DIR:-.jax_compile_cache}/programs-* 2>&1
