#!/bin/bash
# call 7 (1 chip): the FINAL tree's committed files are enough. `_check/final`
# is `git archive $(git write-tree)` (not a git repository, not /root/repo's
# own files): from there the chat cell untraced, the one-chip training cell
# traced (the traced path under the `compared` key), and the probe from a
# root made THERE by the committed tool and probe file.
cd _check/final || exit 1
export PR52_OUT=/root/repo/chiprun_out/pr52
run=perfbench/tools/pr52/run_one.sh
s=$((2147000000 + RANDOM))
bash $run final_chat internlm2-serve-chat $s 0
bash $run final_train1_traced mistral7b-train-1chip $((s + 1)) 1
python3 perfbench/tools/probe.py root perfbench/tools/probes/internlm2-serve-saturated.json _check/probe52
bash $run final_probe internlm2-serve-saturated $((s + 2)) 0 --root _check/probe52
