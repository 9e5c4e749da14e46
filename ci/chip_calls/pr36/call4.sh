# PR 36, call 4: the final tree from `git archive $(git write-tree)` (_check/final), the
# parent (_check/parent = 3f36658) beside it; _check/keep = the final tree with the two lines
# that remove a trace directory (perfbench/lib/xplane.py:reduce_dir, lib/hybrid_replica.py:stats)
# preceded by a copy of it, on this throw-away machine only.
OUT=/root/repo/chiprun_out/pr36; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_compile_cache_call}
run() { # tree cell seed trace tag [extra args]
  local tree=$1 W=$2 seed=$3 tr=$4 tag=$5; shift 5
  (cd _check/$tree && timeout 1500 python3 perfbench/run.py --workload $W --seed $seed --seconds 51 --trace $tr "$@" > $OUT/$tag.log 2>&1; echo rc=$? $tag)
  grep -a "^\[token_path\]\|^\[program_spans\]" $OUT/$tag.log | cut -c 1-1600
  grep -a "^{" $OUT/$tag.log | tail -1 | cut -c 1-${CUT:-6000}
  [ "$tr" = 1 ] && cp _check/$tree/.perfbench_out/$W/last_run.json $OUT/last_run_$tag.json
}
C=internlm2-serve-chat; B=jamba2-serve-chat-burst
run final $C 1000000007 0 c4_warm_c
run final $C 9990005557 1 c4_chat_traced
run parent $C 1230006667 0 c4_chat_p1; run final $C 1230006667 0 c4_chat_f1
run final $C 3450007777 0 c4_chat_f2; run parent $C 3450007777 0 c4_chat_p2
run final $B 1000000007 0 c4_warm_b
run keep $B 9990005557 1 c4_burst_traced
PB=$(ls $OUT/kept_trace/plugins/profile/*/*.xplane.pb 2>/dev/null | tail -1)
ls -la $OUT/kept_trace/plugins/profile/*/ 2>/dev/null
if [ -n "$PB" ]; then
  JAX_PLATFORMS=cpu timeout 900 python3 ci/chip_calls/pr36/idle_gaps.py $PB $OUT/idle_gaps_burst.json > $OUT/idle_gaps_burst.log 2>&1; echo rc=$? idle_gaps
  head -c 9000 $OUT/idle_gaps_burst.json
  gzip -c $PB > $OUT/burst_traced.xplane.pb.gz; ls -la $OUT/*.gz
  [ $(stat -c %s $OUT/burst_traced.xplane.pb.gz) -gt 30000000 ] && rm $OUT/burst_traced.xplane.pb.gz
fi
rm -rf $OUT/kept_trace
# the knee again, two more interleaved pairs: does the instrumentation cost capacity where the host sets it?
run parent $B 2220008887 0 c4_knee_p1 --override rate_per_s=28; run final $B 2220008887 0 c4_knee_f1 --override rate_per_s=28
run final $B 4440009997 0 c4_knee_f2 --override rate_per_s=28; run parent $B 4440009997 0 c4_knee_p2 --override rate_per_s=28
