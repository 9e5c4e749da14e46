# PR 45 call 3 (four chips): which node a chip index opens, then the driver's order in the four-chip training cell,
# no sleep between: the PARENT's tree, this tree at once, the parent's tree at once. Lines carry the time they were printed.
OUT=/root/repo/chiprun_out/pr45/call3; mkdir -p $OUT
python3 ci/chip_calls/pr45/dev_nodes.py --visible ";2;0,1" --node-chips 4 > $OUT/dev_nodes4.log 2>&1
grep -a "^holder\|^links\|^  +\|gone after\|opener right\|^c\|^/dev" $OUT/dev_nodes4.log | cut -c 1-300
run() { (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload mistral7b-train-4chip --seed $3 --seconds 51 --trace 0 2>&1 \
    | while IFS= read -r l; do printf '%s %s\n' "$(date +%s.%3N)" "$l"; done > $OUT/$2.log; echo "rc=${PIPESTATUS[0]} $2 ended $(date +%s.%3N)"
   grep -a " {" $OUT/$2.log | tail -1 | cut -c 1-330; grep -a "\[setup\]\|\[chips\]\|Device or resource busy" $OUT/$2.log | cut -c 1-260 | tail -4; tail -1 $OUT/$2.log | cut -c 1-200); }
run parent a_parent 3100000001
run change b_change_at_once 3100000002
run parent c_parent_at_once 3100000003
run change d_change_at_once 3100000004
