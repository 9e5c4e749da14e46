# PR 64 call 10 (one chip): the tree as handed in, committed files alone (`_check/final` = `git archive $(git write-tree)`):
# `kimi-linear-serve-longgen`, first life then second, the list named as jax names its entries.
ROOT=$PWD; OUT=$ROOT/chiprun_out/pr64/call10; mkdir -p $OUT
d=${JAX_COMPILATION_CACHE_DIR:-.jax_compile_cache}; ls -l $d/programs-* 2>&1 | head
run() { # label seed
  ( cd $ROOT/_check/final && timeout 900 python3 ci/chip_calls/pr64/ahead.py --workload kimi-linear-serve-longgen --seed $2 --seconds 51 --trace 0 > $OUT/$1.log 2>&1 ); echo "rc=$? $1 $(date +%T)"
  grep -a "^{" $OUT/$1.log | tail -1 > $OUT/line_$1.json; python3 ci/chip_calls/pr58/brief.py $OUT/line_$1.json
  grep -a "^\[ahead\]\|^\[programs\]" $OUT/$1.log | cut -c 1-1500
}
run life1 6400000411
run life2 6400000413
ls -l $d/programs-*-cache $d/programs-*-atime 2>&1 | head
