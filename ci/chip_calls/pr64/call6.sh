# PR 64 call 6 (one chip): the committed files alone. `_check/final` = `git archive $(git write-tree)`, `_check/parent` = git
# archive b683862; `kimi-linear-serve-longgen` on one cache directory: the final tree's first life (it records the list; cold
# where the machine's cache lost the cell's programs), then pairs at a seed a pair in the order parent, final, final, parent,
# parent, final, the last of the final tree traced besides.
ROOT=$PWD; OUT=$ROOT/chiprun_out/pr64/${CALL:-call6}; mkdir -p $OUT
CELL=${CELL:-kimi-linear-serve-longgen}
run() { # label tree seed trace
  ( cd $ROOT/_check/$2 && timeout 1500 python3 ci/chip_calls/pr64/ahead.py --workload $CELL --seed $3 --seconds 51 --trace $4 > $OUT/$1.log 2>&1 ); echo "rc=$? $1 $(date +%T)"
  grep -a "^{" $OUT/$1.log | tail -1 > $OUT/line_$1.json; python3 ci/chip_calls/pr58/brief.py $OUT/line_$1.json
  grep -a "^\[ahead\]\|^\[programs\]" $OUT/$1.log | cut -c 1-1500
}
mkdir -p _check/parent/ci/chip_calls/pr64 && cp ci/chip_calls/pr64/ahead.py _check/parent/ci/chip_calls/pr64/
ls -l ${JAX_COMPILATION_CACHE_DIR:-.jax_compile_cache}-programs ${JAX_COMPILATION_CACHE_DIR:-.jax_compile_cache}/programs-* 2>&1 | head -5
run final_life1 final 6400000211 0
run parent_1 parent 6400000213 0
run final_1 final 6400000213 0
run final_2 final 6400000217 0
run parent_2 parent 6400000217 0
run parent_3 parent 6400000219 0
run final_3 final 6400000219 0
run final_4t final 6400000223 1
