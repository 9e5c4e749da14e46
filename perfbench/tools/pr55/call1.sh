#!/bin/bash
# PR 55, call 1: the committed files alone run two cells. Before it, here:
#   git add -A; mkdir -p _check/change; git archive $(git write-tree) | tar -x -C _check/change
#   chiprun --chips 1 --timeout 2400 -- bash perfbench/tools/pr55/call1.sh   (result lines: chiprun_out/pr55/)
set -u
OUT=/root/repo/chiprun_out/pr55; mkdir -p $OUT
cd /root/repo/_check/change || exit 9
run() {  # name, cell, seed, trace
  local t0=$(date +%s)
  python3 perfbench/run.py --workload $2 --seed $3 --seconds 51 --trace $4 > $OUT/$1.out 2> $OUT/$1.err
  echo "$1 rc=$? wall=$(( $(date +%s) - t0 ))s"; tail -n 1 $OUT/$1.out | cut -c1-6000; tail -n 4 $OUT/$1.err | cut -c1-600
}
run chat_cold  internlm2-serve-chat 2147489101 0
run chat_warm  internlm2-serve-chat 2147489102 0
run chat_trace internlm2-serve-chat 2147489103 1
run train_cold mistral7b-train-1chip 2147489104 0
run train_warm mistral7b-train-1chip 2147489105 0
ls /root/repo/_check/change | tr '\n' ' '
