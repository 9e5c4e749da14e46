# PR 58 call 3 (four chips): `mistral7b-train-4chip` traced on the change (a first run that may compile cold, then two warm ones)
# for the ten new metrics of the cell whose worker holds the whole host, and one untraced pair parent / change for `setup_s`.
ROOT=$PWD; OUT=$ROOT/chiprun_out/pr58/call3; mkdir -p $OUT
run() { # tree label seed trace
  (cd $1 && timeout 900 python3 perfbench/run.py --workload mistral7b-train-4chip --seed $3 --seconds 51 --trace $4 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   grep -a "^{" $OUT/$2.log | tail -1 > $OUT/line_$2.json; python3 $ROOT/ci/chip_calls/pr58/brief.py $OUT/line_$2.json
   grep -a "^\[setup\]\|^\[chips\]\|^\[setup_spans\]" $OUT/$2.log | cut -c 1-1200)
}
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}"
run . train4_t1 5800000079 1
run . train4_t2 5800000083 1
run _check/parent train4_p1 5800000089 0
run . train4_c1 5800000089 0
run . train4_t3 5800000097 1
