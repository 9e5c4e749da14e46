# PR 38, call 3 (the log's `_call3.sh`; written as "call 2") (four chips), everything that needs four chips in one call, because a four-chip
# machine came once in six asks:
#  (a) the cell's train step in one process (ci/chip_calls/pr38/step_forms.py): the parent's form,
#      the change's three candidates (plain: the partitioner gathers the weights over fsdp;
#      ring_cols: the column-parallel weights' shards go round fsdp's ring inside the products;
#      ring_all: every weight's) and two forms tried and not kept; parent, plain and ring_all traced;
#  (b) the benchmark's cell on the parent and on the candidate whose step was fastest in (a):
#      parent, change, change, parent untraced (a seed a pair), then one traced run a side.
# _check/parent = git archive of d52e00f; _check/plain = git archive $(git write-tree);
# _check/ring_cols, _check/ring_all = the same with the one line of tp._rides_ring changed.
OUT=/root/repo/chiprun_out/pr38; mkdir -p $OUT
export JAX_COMPILATION_CACHE_DIR=/root/repo/.jax_compile_cache_call
python ci/chip_calls/pr38/step_forms.py --forms parent,change,ring_cols,ring_all,gather_alone,sum_fused,parent \
  --steps 12 --trace parent,change,ring_all --out $OUT/call2 2>&1 | grep -v "^W0\|^I0\|^E0" | tee $OUT/call2_forms.log | cut -c1-2500
BEST=$(python3 - <<'PY'
import json
rows = [json.loads(l) for l in open("/root/repo/chiprun_out/pr38/call2_forms.log") if l.startswith('{"form"')]
ms = {r["form"]: r["step_ms_p50"] for r in rows if "step_ms_p50" in r and r["form"] in ("change", "ring_cols", "ring_all")}
best = min(ms, key=ms.get) if ms else "change"
print({"change": "plain"}.get(best, best))
PY
)
echo "BEST=$BEST"
sleep 20
run() { # tree cell seed trace tag
  local tree=$1 W=$2 seed=$3 tr=$4 tag=$5
  (cd _check/$tree && timeout 900 python3 perfbench/run.py --workload $W --seed $seed --seconds 51 --trace $tr > $OUT/$tag.log 2>&1; echo rc=$? $tag $tree)
  grep -a "^{" $OUT/$tag.log | tail -1 | cut -c 1-${CUT:-1200}
  [ "$tr" = 1 ] && cp _check/$tree/.perfbench_out/$W/last_run.json $OUT/last_run_$tag.json
  sleep 20  # a TPU: 4 worker's chips are free again only a while after it exits
}
C=mistral7b-train-4chip
run parent $C 2147483999 0 t4_p1; run $BEST $C 2147483999 0 t4_c1
run $BEST $C 3050607011 0 t4_c2; run parent $C 3050607011 0 t4_p2
CUT=9000 run $BEST $C 4242424243 1 t4_c_traced
CUT=9000 run parent $C 4242424243 1 t4_p_traced
