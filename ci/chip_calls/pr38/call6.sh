# PR 38, call 6 (four chips; run as `bash ci/chip_calls/pr38/call6.sh`), after the review: the tree
# whose fsdp ring products take their partial sums in float32 (as the partitioner's one product does).
#  (a) the cell's train step in one process (step_forms.py): parent, change, bf16_partials (the
#      reviewed tree's arithmetic), rings_only (the form the review asked for: fsdp's rings under the
#      partitioner's tp all-reduces, no parallel/tp.py); change traced;
#  (b) the benchmark's cell on the parent and on BEST = change, unless rings_only reads within the
#      cell's bound (1%) of it: BEST traced (first: it pays the cold compile), then parent, BEST,
#      BEST, parent untraced, a seed a pair.
# _check/parent = git archive d52e00f; _check/change = git archive $(git write-tree);
# _check/rings_only = the same after ci/chip_calls/pr38/rings_only_tree.py.
OUT=/root/repo/chiprun_out/pr38; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-/root/repo/.jax_compile_cache_call}
python ci/chip_calls/pr38/step_forms.py --forms parent,change,bf16_partials,rings_only \
  --steps 12 --trace change --out $OUT/call6 2>&1 | grep -v "^W0\|^I0\|^E0" | tee $OUT/call6_forms.log | cut -c1-2500
BEST=$(python3 - <<'PY'
import json
rows = [json.loads(l) for l in open("/root/repo/chiprun_out/pr38/call6_forms.log") if l.startswith('{"form"')]
ms = {r["form"]: r["step_ms_p50"] for r in rows if "step_ms_p50" in r}
print("rings_only" if ms.get("rings_only", 1e9) <= 1.01 * ms.get("change", 0) else "change")
PY
)
echo "BEST=$BEST after ${SECONDS}s"
sleep 20
run() { # tree cell seed trace tag
  local tree=$1 W=$2 seed=$3 tr=$4 tag=$5
  (cd _check/$tree && timeout 600 python3 perfbench/run.py --workload $W --seed $seed --seconds 51 --trace $tr > $OUT/$tag.log 2>&1; echo rc=$? $tag $tree at ${SECONDS}s)
  grep -a "^{" $OUT/$tag.log | tail -1 | cut -c 1-${CUT:-1200}
  grep -a "rel_err\|^\[setup\]" $OUT/$tag.log | cut -c1-200
  [ "$tr" = 1 ] && cp _check/$tree/.perfbench_out/$W/last_run.json $OUT/last_run_$tag.json
  sleep 20  # a TPU: 4 worker's chips are free again only a while after it exits
}
C=mistral7b-train-4chip
CUT=9000 run $BEST $C 3141592653 1 r4_c_traced
run parent $C 1357924681 0 r4_p1; run $BEST $C 1357924681 0 r4_c1
run $BEST $C 2604135791 0 r4_c2; run parent $C 2604135791 0 r4_p2
