#!/bin/bash
# call 8 (1 chip): two more traced seeds each of the two cells that newly
# list the twelve token-path metrics (`evabyte-serve-longdoc`,
# `granite4h-serve-ragsessions`): a traced run there whose line lacks one of
# them is refused from now on, and at 0.8 requests/s a reader that wanted a
# prompt pass inside the traced seconds would find none on most seeds.
export JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_compile_cache_call/pr52
unset JAX_COMPILATION_CACHE_MAX_SIZE
run=perfbench/tools/pr52/run_one.sh
s=$((2146900000 + RANDOM))
n=0
for cell in evabyte-serve-longdoc granite4h-serve-ragsessions; do
  for i in 2 3; do
    n=$((n + 1)); bash $run ${cell}_traced$i $cell $((s + n)) 1
  done
done
