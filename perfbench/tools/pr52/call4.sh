#!/bin/bash
# call 4 (1 chip): the committed files are enough. `_check/change` is
# `git archive $(git write-tree)` of the final tree (not a git repository,
# not /root/repo's own files): from there the chat cell untraced and traced,
# and the probe from a root made THERE by the committed tool and probe file.
cd _check/change || exit 1
export PR52_OUT=/root/repo/chiprun_out/pr52
run=perfbench/tools/pr52/run_one.sh
s=$((2147200000 + RANDOM))
bash $run archive_chat internlm2-serve-chat $s 0
bash $run archive_chat_traced internlm2-serve-chat $((s + 1)) 1
python3 perfbench/tools/probe.py root perfbench/tools/probes/internlm2-serve-saturated.json _check/probe52
bash $run archive_probe internlm2-serve-saturated $((s + 2)) 0 --root _check/probe52
# then whether the replica's own trace timer (`trace_between`) changed what a
# traced run of the most profiler-sensitive cell reads: `_check/parent` is
# `git archive` of the parent commit; parent, change, parent, one seed.
cd /root/repo
export JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_compile_cache_call/pr52
unset JAX_COMPILATION_CACHE_MAX_SIZE
for side in parent change parent2; do
  (cd _check/${side%2} && bash /root/repo/perfbench/tools/pr52/run_one.sh ab_burst_traced_$side \
     jamba2-serve-chat-burst $((s + 7)) 1)
done
