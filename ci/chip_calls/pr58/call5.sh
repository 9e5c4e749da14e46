# PR 58 call 5 (one chip): one traced run of each cell that calls 1-4 left out, on the change: every cell's traced line has to
# carry the new metrics that list it, none None. The machine's cache holds 190 MiB: a cell whose programs were evicted compiles cold.
ROOT=$PWD; OUT=$ROOT/chiprun_out/pr58/call5; mkdir -p $OUT
run() { # label cell seed
  timeout 1200 python3 perfbench/run.py --workload $2 --seed $3 --seconds 51 --trace 1 > $OUT/$1.log 2>&1; echo "rc=$? $1 $(date +%T)"
  grep -a "^{" $OUT/$1.log | tail -1 > $OUT/line_$1.json; python3 ci/chip_calls/pr58/brief.py $OUT/line_$1.json
  grep -a "^\[setup\]\|^\[chips\]\|^\[setup_spans\]" $OUT/$1.log | cut -c 1-1200
}
run burst_t jamba2-serve-chat-burst 5800000141
run longctx_t openpangu-serve-longctx 5800000149
run longdoc_t evabyte-serve-longdoc 5800000153
run rag_t granite4h-serve-ragsessions 5800000159
run docqa_t keye-vl2-serve-docqa 5800000167
