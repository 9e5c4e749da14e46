#!/bin/bash
# call 6 (4 CHIPS): `mistral7b-train-4chip`, the one cell that exists only
# across chips, twice untraced (the first compiles) on the call's own compile
# cache. Its driver and worker are untouched: only `run.py` above it changed
# (the `compared` key and stderr's last lines). Nothing else runs here: a
# second on four chips costs four.
export JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_compile_cache_call/pr52
unset JAX_COMPILATION_CACHE_MAX_SIZE
run=perfbench/tools/pr52/run_one.sh
s=$((2147100000 + RANDOM))
for i in 1 2; do
  bash $run train4_$i mistral7b-train-4chip $((s + i)) 0
done
