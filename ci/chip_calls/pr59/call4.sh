# PR 59 call 4 (four chips): two more pairs of the claimed cell at fresh seeds, _check/final against _check/parent: final, parent,
# parent, final (the other side first this time).
OUT=/root/repo/chiprun_out/pr59/call4; mkdir -p $OUT
run() { # tree label seed
  (cd _check/$1 && timeout 900 python3 perfbench/run.py --workload mistral7b-train-4chip --seed $3 --seconds 51 --trace 0 > $OUT/$2.log 2>&1; echo "rc=$? $2 $(date +%T)"
   grep -a "^{" $OUT/$2.log | tail -1 | cut -c 1-700)
}
run final f3 5930000063
run parent p3 5930000063
run parent p4 5940000081
run final f4 5940000081
