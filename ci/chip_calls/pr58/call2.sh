# PR 58 call 2 (one chip): `kimi-linear-serve-longgen` traced twice on the change (the ten new metrics, the five longest cache
# reads, hits and misses of its programs, whether the donated `decode_step` hits in the cell's own worker). The first run of the
# call may compile cold (no export of JAX_COMPILATION_CACHE_DIR here: the machine's cache, if any, is in force); the second is warm.
ROOT=$PWD; OUT=$ROOT/chiprun_out/pr58/call2; mkdir -p $OUT
run() { # label cell seed trace
  timeout 1500 python3 perfbench/run.py --workload $2 --seed $3 --seconds 51 --trace $4 > $OUT/$1.log 2>&1; echo "rc=$? $1 $(date +%T)"
  grep -a "^{" $OUT/$1.log | tail -1 > $OUT/line_$1.json; python3 ci/chip_calls/pr58/brief.py $OUT/line_$1.json
  grep -a "^\[setup\]\|^\[chips\]\|^\[setup_spans\]" $OUT/$1.log | cut -c 1-1200
}
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}"
run longgen_t1 kimi-linear-serve-longgen 5800000067 1
run longgen_t2 kimi-linear-serve-longgen 5800000071 1
run longgen_t3 kimi-linear-serve-longgen 5800000073 1
