#!/bin/bash
# call 1 (1 chip): the probe. `internlm2-serve-saturated` as
# tools/probes/internlm2-serve-saturated.json states it, from a benchmark
# root of its own (BENCHMARK.json + the appended entries, one traffic file,
# NO new configuration), on this tree's program: three seeds untraced, one
# traced; then the chat cell, which shares its model and configuration,
# three seeds untraced: its environment is the parent's.
python3 perfbench/tools/probe.py root perfbench/tools/probes/internlm2-serve-saturated.json _check/probe52
run=perfbench/tools/pr52/run_one.sh
s=$((2147483000 + RANDOM))
for i in 1 2 3; do
  bash $run probe_$i internlm2-serve-saturated $((s + i)) 0 --root _check/probe52
done
bash $run probe_traced internlm2-serve-saturated $((s + 4)) 1 --root _check/probe52
for i in 1 2 3; do
  bash $run chat_$i internlm2-serve-chat $((s + 10 + i)) 0
done
