#!/bin/bash
# PR 63, call 6: the accepted cell that shares most code with the change (the
# window form: Command A+'s), whose programs lower to the parent's text
# (`lowered_hash.py`): traced on the PARENT's checkout with this PR's benchmark
# files laid over it (_check/parent63, as the driver lays them: the new readers
# must read nothing there and raise nothing), then untraced on the parent and
# on the committed files (_check/final63), one seed for both.
mkdir -p chiprun_out/pr63
cell=command-a-plus-serve-mixedqueue
run() {   # <dir> <tag> <trace> <seed>
  ln -sfn "$PWD/chiprun_out" $1/chiprun_out
  (cd $1 && python3 perfbench/run.py --workload $cell --seed $4 --seconds 51 --trace $3 \
     > chiprun_out/pr63/$2.out 2> chiprun_out/pr63/$2.err; echo "$2 rc $?"
   grep -E "^\[correct\]" chiprun_out/pr63/$2.out | tail -6; tail -n 1 chiprun_out/pr63/$2.out | cut -c1-${5:-500})
}
run _check/parent63 cmda_parent_traced 1 2147480421 6000
run _check/parent63 cmda_parent 0 2147480422
run _check/final63 cmda_final 0 2147480422
