# PR 61 call 8 (four chips): two more pairs of the claimed cell from the tree as committed (_check/final = git archive $(git write-tree),
# after the clean-up), the other side first: final, parent, parent, final at fresh seeds.
OUT=/root/repo/chiprun_out/pr61/call8; mkdir -p $OUT
run() { # tree label seed
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload mistral7b-train-4chip --seed $3 --seconds 51 --trace 0 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-700)
}
run final f3 6130000051
run parent p3 6130000051
run parent p4 6140000069
run final f4 6140000069
