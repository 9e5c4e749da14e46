# PR 58 call 1 (one chip): what the set-up spans cost when they are on (they are on by default), and the split they give.
# `internlm2-serve-chat` and `mistral7b-train-1chip` untraced, parent (_check/parent = git archive 47c78e5) and change (the tree as
# it stands) ALTERNATED, a seed a pair (within one call `setup_s` falls run after run whichever tree runs: ROADMAP A7); then the
# change traced twice a cell for the ten new metrics. The machine's compile cache is left in force (nothing exported here).
ROOT=$PWD; OUT=$ROOT/chiprun_out/pr58/call1; mkdir -p $OUT
run() { # tree label cell seed trace
  (cd $1 && timeout 900 python3 perfbench/run.py --workload $3 --seed $4 --seconds 51 --trace $5 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   grep -a "^{" $OUT/$2.log | tail -1 > $OUT/line_$2.json; python3 $ROOT/ci/chip_calls/pr58/brief.py $OUT/line_$2.json
   grep -a "^\[setup\]\|^\[chips\]\|^\[setup_spans\]" $OUT/$2.log | cut -c 1-900)
}
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}"
run _check/parent chat_p1 internlm2-serve-chat 5800000011 0
run . chat_c1 internlm2-serve-chat 5800000011 0
run _check/parent chat_p2 internlm2-serve-chat 5800000023 0
run . chat_c2 internlm2-serve-chat 5800000023 0
run _check/parent train_p1 mistral7b-train-1chip 5800000037 0
run . train_c1 mistral7b-train-1chip 5800000037 0
run _check/parent train_p2 mistral7b-train-1chip 5800000041 0
run . train_c2 mistral7b-train-1chip 5800000041 0
run . chat_t1 internlm2-serve-chat 5800000053 1
run . chat_t2 internlm2-serve-chat 5800000059 1
