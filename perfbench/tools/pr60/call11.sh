#!/bin/bash
# PR 60, call 11 (after the benchmark check's refusal: `tools/pr58/entries.json`
# restored, this PR's entries moved to the END of `BENCHMARK.json`'s lists):
# from the COMMITTED files alone (_check/rev), three seeds of the new cell;
# then the parent with this PR's benchmark files laid over it (_check/parent):
# the new cell (it must fail cleanly, soon) and one old cell traced.
mkdir -p chiprun_out/pr60
ln -sfn "$PWD/chiprun_out" _check/rev/chiprun_out
(cd _check/rev && bash perfbench/tools/pr60/cell.sh g_sound 0 -- 2147481101 2147481102 2147481103)
t0=$(date +%s)
(cd _check/parent && python3 perfbench/run.py --workload command-a-plus-serve-mixedqueue \
  --seed 2147481111 --seconds 51 --trace 0) > chiprun_out/pr60/g_parent_new.out 2> chiprun_out/pr60/g_parent_new.err
echo "parent, new cell: rc $? in $(( $(date +%s) - t0 )) s: $(tail -n 2 chiprun_out/pr60/g_parent_new.err | tr '\n' ' ' | cut -c1-300)"
(cd _check/parent && python3 perfbench/run.py --workload internlm2-serve-chat \
  --seed 2147481121 --seconds 51 --trace 1) > chiprun_out/pr60/g_parent_traced_internlm2.out 2> chiprun_out/pr60/g_parent_traced_internlm2.err
echo "parent + this PR's benchmark files, internlm2-serve-chat traced: rc $?: $(tail -n 1 chiprun_out/pr60/g_parent_traced_internlm2.out | cut -c1-1500)"
