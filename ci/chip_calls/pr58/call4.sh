# PR 58 call 4 (one chip): `mistral7b-train-1chip` again, because call 1's second run of the change read 14,584.8 tokens/s/chip
# (182 steps where 195-196 fit) beside 15,670-15,695 in the three others: change and parent alternated, the change FIRST this
# time, a seed a pair, then the change traced twice (step time, idle share, and the ten new metrics of the cell).
ROOT=$PWD; OUT=$ROOT/chiprun_out/pr58/call4; mkdir -p $OUT
run() { # tree label seed trace
  (cd $1 && timeout 900 python3 perfbench/run.py --workload mistral7b-train-1chip --seed $3 --seconds 51 --trace $4 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   grep -a "^{" $OUT/$2.log | tail -1 > $OUT/line_$2.json; python3 $ROOT/ci/chip_calls/pr58/brief.py $OUT/line_$2.json
   python3 $ROOT/ci/chip_calls/pr58/steps.py .perfbench_out/mistral7b-train-1chip/last_run.json
   grep -a "^\[setup\]\|^\[chips\]\|^\[setup_spans\]" $OUT/$2.log | cut -c 1-1200)
}
run . train_c3 5800000101 0
run _check/parent train_p3 5800000101 0
run . train_c4 5800000107 0
run _check/parent train_p4 5800000107 0
run . train_c5 5800000113 0
run _check/parent train_p5 5800000113 0
run . train_t1 5800000127 1
run . train_t2 5800000131 1
# and the parent with this PR's benchmark files laid over it (_check/overlay: git archive 47c78e5 + BENCHMARK.json, perfbench/,
# tests/perfbench/ of this tree), traced: a program without the spans gives no number and no error
run _check/overlay train_overlay_t 5800000137 1
