#!/bin/bash
# call 3 (1 chip): CELLS (default: the two list-form serving cells), three
# seeds untraced and one traced each, on the call's own compile cache; with
# TRAIN=1 the one-chip training cell twice before them (its driver is
# untouched: only `run.py` above it changed).
export JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_compile_cache_call/pr52
unset JAX_COMPILATION_CACHE_MAX_SIZE
run=perfbench/tools/pr52/run_one.sh
s=$((2147300000 + RANDOM))
n=0
if [ -n "$TRAIN" ]; then
  for i in 1 2; do
    n=$((n + 1)); bash $run train1_$i mistral7b-train-1chip $((s + n)) 0
  done
fi
for cell in ${CELLS:-kimi-linear-serve-longgen openpangu-serve-longctx}; do
  for i in 1 2 3; do
    n=$((n + 1)); bash $run ${cell}_$i $cell $((s + n)) 0
  done
  n=$((n + 1)); bash $run ${cell}_traced $cell $((s + n)) 1
done
