# PR 58 call 6 (one chip): the tree as git would commit it (_check/final = git archive $(git write-tree)), whose BENCHMARK.json
# is the parent's byte for byte: the ten metrics are read from a root of their own (perfbench/tools/pr58/root.py appends
# perfbench/tools/pr58/entries.json), the cells themselves run as the driver runs them. Chat untraced (the driver's command),
# chat and train-1chip traced under the root.
ROOT=$PWD; OUT=$ROOT/chiprun_out/pr58/call6; mkdir -p $OUT
cd _check/final && python3 perfbench/tools/pr58/root.py _check/setup_root
run() { # label cell seed trace [root]
  timeout 900 python3 perfbench/run.py ${5:+--root $5} --workload $2 --seed $3 --seconds 51 --trace $4 > $OUT/$1.log 2>&1; echo "rc=$? $1 $(date +%T)"
  grep -a "^{" $OUT/$1.log | tail -1 > $OUT/line_$1.json; python3 $ROOT/ci/chip_calls/pr58/brief.py $OUT/line_$1.json
  grep -a "^\[setup\]\|^\[chips\]\|^\[setup_spans\]" $OUT/$1.log | cut -c 1-1200
}
run chat_f1 internlm2-serve-chat 5800000171 0
run chat_ft internlm2-serve-chat 5800000179 1 _check/setup_root
run chat_ft_plain internlm2-serve-chat 5800000183 1
run train_ft mistral7b-train-1chip 5800000191 1 _check/setup_root
