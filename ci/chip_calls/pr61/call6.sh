# PR 61 call 6 (four chips): the tree as git would commit it (_check/final = git archive $(git write-tree)) against _check/parent
# (git archive bcbfb84): `mistral7b-train-4chip` untraced at fresh seeds parent, final, final, parent (the first of a tree compiles
# cold unless the machine's cache has it), then a traced pair.
OUT=/root/repo/chiprun_out/pr61/call6; mkdir -p $OUT
run() { # tree label seed trace
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload mistral7b-train-4chip --seed $3 --seconds 51 --trace $4 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   cp .perfbench_out/mistral7b-train-4chip/last_run.json $OUT/last_run_$2.json 2>/dev/null
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-900; grep -a "^\[setup\]\|^\[chips\]\|^\[correct\]" $OUT/$2.log | cut -c 1-200)
}
run parent p1 6100000019 0
run final f1 6100000019 0
run final f2 6100000033 0
run parent p2 6100000033 0
run parent p_traced 6100000047 1
run final f_traced 6100000047 1
for f in p_traced f_traced; do grep -a "^{" $OUT/$f.log | tail -1 > $OUT/line_$f.json; done
