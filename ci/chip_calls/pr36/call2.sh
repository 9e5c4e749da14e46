# PR 36, call 2: jamba2-serve-chat-burst, parent against change.
# _check/parent = git archive of 3f36658 (its own benchmark files: untraced pairs)
# _check/parent_new = the parent's program under this PR's benchmark files (what the
#   driver runs traced: the nine new readers must give None there and the run must end)
# _check/change = git archive $(git write-tree)
# _check/keep = _check/change with ONE line of perfbench/lib/xplane.py:reduce_dir patched
#   (on this throw-away machine only) so that the trace directory is copied before it is removed
OUT=/root/repo/chiprun_out/pr36; mkdir -p $OUT
# one compile cache for the four trees of a call: their jitted programs are the same text
export JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_compile_cache_call
run() { # tree cell seed trace tag [extra args]
  local tree=$1 W=$2 seed=$3 tr=$4 tag=$5; shift 5
  (cd _check/$tree && timeout 900 python3 perfbench/run.py --workload $W --seed $seed --seconds 51 --trace $tr "$@" > $OUT/$tag.log 2>&1; echo rc=$? $tag)
  grep -a "^\[token_path\]\|^\[program_spans\]" $OUT/$tag.log | cut -c 1-400
  grep -a "^{" $OUT/$tag.log | tail -1 | cut -c 1-${CUT:-700}
  [ "$tr" = 1 ] && cp _check/$tree/.perfbench_out/$W/last_run.json $OUT/last_run_$tag.json
}
C=internlm2-serve-chat; B=jamba2-serve-chat-burst
# chat-burst: two interleaved pairs untraced, the knee (28/s by --override) once a side
run change $B 1000000007 0 warm_b
run parent $B 2147483777 0 burst_p1; run change $B 2147483777 0 burst_c1
run change $B 3141592653 0 burst_c2; run parent $B 3141592653 0 burst_p2
CUT=6000 run parent $B 1618033989 0 knee_p --override rate_per_s=28
CUT=6000 run change $B 1618033989 0 knee_c --override rate_per_s=28
# traced: the change with the trace kept, the parent under the new files
CUT=6000 run keep $B 4242424243 1 burst_c_traced
CUT=6000 run parent_new $B 4242424243 1 burst_pn_traced
PB=$(ls $OUT/kept_trace/plugins/profile/*/*.xplane.pb 2>/dev/null | tail -1)
ls -la $OUT/kept_trace/plugins/profile/*/ 2>/dev/null
if [ -n "$PB" ]; then
  JAX_PLATFORMS=cpu timeout 600 python3 ci/chip_calls/pr36/idle_gaps.py $PB $OUT/idle_gaps_burst.json > $OUT/idle_gaps_burst.log 2>&1; echo rc=$? idle_gaps
  head -c 5000 $OUT/idle_gaps_burst.json
  gzip -c $PB > $OUT/burst_c_traced.xplane.pb.gz; ls -la $OUT/*.gz
  # what comes back is capped at 64 MiB: the reading above is what matters
  [ $(stat -c %s $OUT/burst_c_traced.xplane.pb.gz) -gt 30000000 ] && rm $OUT/burst_c_traced.xplane.pb.gz
fi
rm -rf $OUT/kept_trace
