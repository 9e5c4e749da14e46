# PR 58 call 8 (four chips), after the review: `mistral7b-train-4chip` traced twice on the final tree (_check/final) under the
# root that holds the ten entries: the cell whose worker holds the whole host, through the guarded `chip.open` and the plain sums.
ROOT=$PWD; OUT=$ROOT/chiprun_out/pr58/call8; mkdir -p $OUT
cd _check/final && python3 perfbench/tools/pr58/root.py _check/setup_root
run() { # label seed
  timeout 900 python3 perfbench/run.py --root _check/setup_root --workload mistral7b-train-4chip --seed $2 --seconds 51 --trace 1 > $OUT/$1.log 2>&1; echo "rc=$? $1 $(date +%T)"
  grep -a "^{" $OUT/$1.log | tail -1 > $OUT/line_$1.json; python3 $ROOT/ci/chip_calls/pr58/brief.py $OUT/line_$1.json
  grep -a "^\[setup\]\|^\[chips\]\|^\[setup_spans\]" $OUT/$1.log | cut -c 1-1200
}
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}"
run train4_ft1 5800000223
run train4_ft2 5800000227
