# PR 36, call 6: chat-burst traced with the trace KEPT, twice (a traced run of this cell is
# not always a clean one: call 4's kept trace held 0.25 s of a backlog's prompt passes), each
# read by ci/chip_calls/pr36/idle_gaps.py with all of the program's host spans.
# _check/keep = the final tree with the two lines that remove a trace directory preceded by a copy.
OUT=/root/repo/chiprun_out/pr36; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_compile_cache_call}
B=jamba2-serve-chat-burst
for seed in 2468013579 1123581321; do
  tag=c6_burst_traced_$seed
  (cd _check/keep && timeout 1500 python3 perfbench/run.py --workload $B --seed $seed --seconds 51 --trace 1 > $OUT/$tag.log 2>&1; echo rc=$? $tag)
  grep -a "^\[token_path\]\|^\[program_spans\]" $OUT/$tag.log | cut -c 1-1800
  grep -a "^{" $OUT/$tag.log | tail -1 | cut -c 1-6000
  cp _check/keep/.perfbench_out/$B/last_run.json $OUT/last_run_$tag.json
  PB=$(ls $OUT/kept_trace/plugins/profile/*/*.xplane.pb 2>/dev/null | tail -1)
  if [ -n "$PB" ]; then
    JAX_PLATFORMS=cpu timeout 900 python3 ci/chip_calls/pr36/idle_gaps.py $PB $OUT/idle_gaps_$seed.json > $OUT/idle_gaps_$seed.log 2>&1; echo rc=$? idle_gaps
    head -c 7000 $OUT/idle_gaps_$seed.json
    gzip -c $PB > $OUT/burst_$seed.xplane.pb.gz; ls -la $OUT/burst_$seed.xplane.pb.gz
    [ $(stat -c %s $OUT/burst_$seed.xplane.pb.gz) -gt 25000000 ] && rm $OUT/burst_$seed.xplane.pb.gz
  fi
  rm -rf $OUT/kept_trace
done
true
