# PR 64 call 8 (one chip): whether the lists inside the cache directory came with the machine (call 7 left them);
# `kimi-linear-serve-longgen` from the committed files alone (`_check/final` = `git archive $(git write-tree)`, the list now
# inside the cache directory): first life, second life; then the last three serving cells as call 7 ran its own (`call5.sh`).
ROOT=$PWD; OUT=$ROOT/chiprun_out/pr64/call8; mkdir -p $OUT
ls -l ${JAX_COMPILATION_CACHE_DIR:-.jax_compile_cache}/programs-* 2>&1 | head
run() { # label tree cell seed
  ( cd $ROOT/_check/$2 && timeout 1500 python3 $ROOT/ci/chip_calls/pr64/ahead.py --workload $3 --seed $4 --seconds 51 --trace 0 > $OUT/$1.log 2>&1 ); echo "rc=$? $1 $(date +%T)"
  grep -a "^{" $OUT/$1.log | tail -1 > $OUT/line_$1.json; python3 ci/chip_calls/pr58/brief.py $OUT/line_$1.json
  grep -a "^\[ahead\]\|^\[programs\]" $OUT/$1.log | cut -c 1-1500
}
run kimi_final_life1 final kimi-linear-serve-longgen 6400000311
run kimi_final_life2 final kimi-linear-serve-longgen 6400000313
CELLS="internlm2-serve-chat evabyte-serve-longdoc command-a-plus-serve-mixedqueue" CALL=call8 bash ci/chip_calls/pr64/call5.sh
ls -l ${JAX_COMPILATION_CACHE_DIR:-.jax_compile_cache}/programs-* 2>&1 | head -20
