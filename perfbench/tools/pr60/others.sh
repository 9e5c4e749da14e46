#!/bin/bash
# PR 60: what the change must not move, and the parent on the new cell.
# `_check/parent` is the parent commit (`git archive`) with this PR's
# benchmark files laid over it (BENCHMARK.json, perfbench/, tests/perfbench/).
# parent, change, change, parent in one call, on one chip.
mkdir -p chiprun_out/pr60
run() {  # <dir> <tag> <workload> <seed>
  (cd $1 && python3 perfbench/run.py --workload $3 --seed $4 --seconds 51 --trace 0) \
    > chiprun_out/pr60/$2_$3_s$4.out 2> chiprun_out/pr60/$2_$3_s$4.err
  echo "$2 $3 seed $4 rc $?: $(tail -n 1 chiprun_out/pr60/$2_$3_s$4.out | cut -c1-260)"
}
# the parent cannot run the new cell: it says so at once, exit code not 0
t0=$(date +%s)
run _check/parent parent command-a-plus-serve-mixedqueue 2147480401
echo "the parent took $(( $(date +%s) - t0 )) s on the new cell: $(tail -n 2 chiprun_out/pr60/parent_command-a-plus-serve-mixedqueue_s2147480401.err | tr '\n' ' ' | cut -c1-300)"
for cell in "$@"; do
  run _check/parent parent $cell 2147480411
  run . change $cell 2147480411
  run . change $cell 2147480412
  run _check/parent parent $cell 2147480412
done
