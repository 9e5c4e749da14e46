# PR 58 call 7 (one chip), after the review: the tree as git would commit it (_check/final = git archive $(git write-tree)) with
# `engine.init` and the print-only arguments gone, `chip.open`'s recording half guarded and the reader's compile sums plain.
# chat untraced, parent (_check/parent = git archive 47c78e5) then the final tree at one seed; then the final tree traced under
# the root that holds the ten entries (perfbench/tools/pr58/root.py): chat, train-1chip (a task's lease) and longgen (57 programs).
ROOT=$PWD; OUT=$ROOT/chiprun_out/pr58/call7; mkdir -p $OUT
(cd _check/final && python3 perfbench/tools/pr58/root.py _check/setup_root)
run() { # tree label cell seed trace [root]
  (cd $1 && timeout 1500 python3 perfbench/run.py ${6:+--root $6} --workload $3 --seed $4 --seconds 51 --trace $5 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   grep -a "^{" $OUT/$2.log | tail -1 > $OUT/line_$2.json; python3 $ROOT/ci/chip_calls/pr58/brief.py $OUT/line_$2.json
   grep -a "^\[setup\]\|^\[chips\]\|^\[setup_spans\]" $OUT/$2.log | cut -c 1-1200)
}
echo "JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR:-unset}"
run _check/parent chat_p1 internlm2-serve-chat 5800000201 0
run _check/final chat_f1 internlm2-serve-chat 5800000201 0
run _check/final chat_ft internlm2-serve-chat 5800000207 1 _check/setup_root
run _check/final train_ft mistral7b-train-1chip 5800000213 1 _check/setup_root
run _check/final longgen_ft kimi-linear-serve-longgen 5800000219 1 _check/setup_root
