#!/bin/bash
# call 11 (1 chip): the tree as it goes to the check, from `_check/final2` =
# `git archive $(git write-tree)` (not a git repository, not /root/repo's own
# files). The chat cell with its traffic file's `order_seed` 9 and the bound
# of 0.1: one traced run (every per-layer metric of the cell, the new
# `serve.tpot_p50_ms` among them), then six untraced runs, a seed each: the
# spread of `tpot_p95_ms` anew on the committed files.
cd _check/final2 || exit 1
export PR52_OUT=/root/repo/chiprun_out/pr52
export JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_compile_cache_call/pr52
unset JAX_COMPILATION_CACHE_MAX_SIZE
run=perfbench/tools/pr52/run_one.sh
cell=internlm2-serve-chat
s=$((2147200000 + RANDOM))
bash $run final2_traced $cell $s 1
for i in 1 2 3 4 5 6; do
  bash $run final2_$i $cell $((s + i)) 0
  python3 perfbench/tools/pr52/tail.py .perfbench_out/$cell/last_run.json
  cp .perfbench_out/$cell/last_run.json $PR52_OUT/lastrun_final2_$i.json
done
