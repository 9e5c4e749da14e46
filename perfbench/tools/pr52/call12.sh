#!/bin/bash
# call 12 (1 chip): the tree as it goes to the check, again from
# `_check/final2` = `git archive $(git write-tree)`: the chat cell with the
# median end to end (`tpot_p50_ms`) and the tail per layer
# (`serve.tpot_p95_ms`). One untraced run first (it compiles in this path),
# then the traced run WARM: call 11's traced run was the path's first, on a
# host that stood still for 0.12 s several times a run, and shed 9 requests
# while the profiler wrote its trace for 78 s.
cd _check/final2 || exit 1
export PR52_OUT=/root/repo/chiprun_out/pr52
export JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_compile_cache_call/pr52
unset JAX_COMPILATION_CACHE_MAX_SIZE
run=perfbench/tools/pr52/run_one.sh
cell=internlm2-serve-chat
s=$((2147300000 + RANDOM))
bash $run final3_1 $cell $s 0
cp .perfbench_out/$cell/last_run.json $PR52_OUT/lastrun_final3_1.json
bash $run final3_traced $cell $((s + 1)) 1
python3 perfbench/tools/pr52/tail.py .perfbench_out/$cell/last_run.json
