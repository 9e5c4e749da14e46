# PR 38, call 5 (the log's `_call5.sh`; written as "call 4") (four chips): the FINAL tree from `git archive $(git write-tree)` (_check/final)
# beside the parent (_check/parent = git archive d52e00f): mistral7b-train-4chip one pair untraced,
# the change traced; then `chip_smoke.py --chips 4` of the final tree.
OUT=/root/repo/chiprun_out/pr38; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_compile_cache_call
run() { # tree cell seed trace tag
  local tree=$1 W=$2 seed=$3 tr=$4 tag=$5
  (cd _check/$tree && timeout 900 python3 perfbench/run.py --workload $W --seed $seed --seconds 51 --trace $tr > $OUT/$tag.log 2>&1; echo rc=$? $tag $tree)
  grep -a "^{" $OUT/$tag.log | tail -1 | cut -c 1-${CUT:-1200}
  grep -a "rel_err\|^\[setup\]" $OUT/$tag.log | cut -c1-200
  [ "$tr" = 1 ] && cp _check/$tree/.perfbench_out/$W/last_run.json $OUT/last_run_$tag.json
  sleep 20  # a TPU: 4 worker's chips are free again only a while after it exits
}
C=mistral7b-train-4chip
run final $C 1618033989 0 f4_c1; run parent $C 1618033989 0 f4_p1
CUT=9000 run final $C 2236067977 1 f4_c_traced
(cd _check/final && timeout 900 python3 chip_smoke.py --chips 4 > $OUT/smoke4.log 2>&1; echo rc=$? chip_smoke)
grep -a "loss_abs_diff_vs_1chip\|collectives\|\"ok\"" $OUT/smoke4.log | cut -c1-600 | tail -6
sleep 20
