#!/bin/bash
# PR 60, call 10: the committed files alone (_check/rev) once more, with the
# readers as they are left (the expert step's roofline from the MEDIAN step's
# bytes): a traced run and one more seed.
mkdir -p chiprun_out/pr60
ln -sfn "$PWD/chiprun_out" _check/rev/chiprun_out
cd _check/rev
bash perfbench/tools/pr60/cell.sh f_traced 1 -- 2147480813
bash perfbench/tools/pr60/cell.sh f_sound 0 -- 2147480807
