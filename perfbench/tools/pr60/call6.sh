#!/bin/bash
# PR 60, call 6, four chips: the training cell whose flash kernel's forward
# gained two arguments, parent, change, change, parent.
mkdir -p chiprun_out/pr60
run() {  # <dir> <tag> <seed>
  (cd $1 && python3 perfbench/run.py --workload mistral7b-train-4chip --seed $3 --seconds 51 --trace 0) \
    > chiprun_out/pr60/$2_mistral7b-train-4chip_s$3.out 2> chiprun_out/pr60/$2_mistral7b-train-4chip_s$3.err
  echo "$2 seed $3 rc $?: $(tail -n 1 chiprun_out/pr60/$2_mistral7b-train-4chip_s$3.out | cut -c1-260)"
}
run _check/parent parent 2147480411
run . change 2147480411
run . change 2147480412
run _check/parent parent 2147480412
