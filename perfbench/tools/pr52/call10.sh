#!/bin/bash
# call 10 (1 chip; after the check's refusal: `tpot_p95_ms` of
# `internlm2-serve-chat` spread 0.284 and 0.355 ms against a bound of 0.257).
# Chips were scarce (two calls were handed none), so the refusal's steps are
# gathered into one command, in their order:
#  steps 1 and 2, the cell AS REFUSED (its traffic file is untouched; the
#    generator's `order_seed` key is inert without it): one seed twice (the
#    first run compiles: held against the second), two other seeds once;
#  step 3, the cure tried: six runs, a seed each, with ONE order for every
#    seed (`--override order_seed=9`: the line says `not_the_cell`, the
#    programs and the load are the cell's).
# Each run's record is kept under chiprun_out/pr52/ for the tail to be read.
export JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_compile_cache_call/pr52
unset JAX_COMPILATION_CACHE_MAX_SIZE
run=perfbench/tools/pr52/run_one.sh
cell=internlm2-serve-chat
s=$((2147100000 + RANDOM))
one() {  # tag seed [--override ...]
  tag=$1 seed=$2; shift 2
  bash $run $tag $cell $seed 0 "$@"
  python3 perfbench/tools/pr52/tail.py .perfbench_out/$cell/last_run.json
  cp .perfbench_out/$cell/last_run.json chiprun_out/pr52/lastrun_$tag.json
}
i=0
for seed in $s $s $((s + 1)) $((s + 2)); do
  i=$((i + 1)); one step1_$i $seed
done
for i in 1 2 3 4 5 6; do
  one step3_$i $((s + 10 + i)) --override order_seed=9
done
