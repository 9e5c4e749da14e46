#!/bin/bash
# PR 60, call 4: the readings behind the limits at the cell's rate: six sound
# seeds, three under the int8 control, one under no_window.
bash perfbench/tools/pr60/cell.sh sound 0 -- 2147480501 2147480502 2147480503 2147480504 2147480505 2147480506
bash perfbench/tools/pr60/cell.sh int8 0 --control int8 -- 2147480511 2147480512 2147480513
bash perfbench/tools/pr60/cell.sh no_window 0 --control no_window -- 2147480521
