#!/bin/bash
# call 2 (1 chip): after call 1 showed the traced probe's `trace_stop` 16 s
# late behind ~1,000 queued requests, the replica times its own trace
# (`trace_between`): the probe traced again; then three serving cells whose
# drivers and replicas this PR touched, each three seeds untraced and one
# traced, on a compile cache of the call's own (the machine's is capped at
# 192 MiB, less than two cells' programs): `evabyte-serve-longdoc` and
# `granite4h-serve-ragsessions` (traced: all twelve token-path metrics, new
# in their lists) and `jamba2-serve-chat-burst` (the cell a profiler stalls
# most).
export JAX_COMPILATION_CACHE_DIR=$PWD/.jax_compile_cache_call/pr52
unset JAX_COMPILATION_CACHE_MAX_SIZE
python3 perfbench/tools/probe.py root perfbench/tools/probes/internlm2-serve-saturated.json _check/probe52
run=perfbench/tools/pr52/run_one.sh
s=$((2147400000 + RANDOM))
bash $run probe_traced2 internlm2-serve-saturated $s 1 --root _check/probe52
n=0
for cell in ${CELLS:-evabyte-serve-longdoc granite4h-serve-ragsessions jamba2-serve-chat-burst}; do
  for i in 1 2 3; do
    n=$((n + 1)); bash $run ${cell}_$i $cell $((s + n)) 0
  done
  n=$((n + 1)); bash $run ${cell}_traced $cell $((s + n)) 1
done
