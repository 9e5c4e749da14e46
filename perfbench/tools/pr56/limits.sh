#!/bin/bash
# PR 56, second session, call 11: the readings behind the four limits once the
# reference follows the program's choices over an answer's WHOLE length
# (`check_decode_steps` 384): a sound run, an `int8` run and the `every_row`
# run first; if the sound run's `token_gap_mean_spacings` is not under 1 the
# call ends there (the reading would be the old one: nothing to set a limit
# from). Then two more sound seeds, two more `int8` seeds and a traced run
# (the 10 s trace window). From the checkout's root:
#   chiprun --timeout 3300 -- bash perfbench/tools/pr56/limits.sh
set -u
export ROOT_DIR=.
cell() { bash perfbench/tools/pr56/cell.sh "$@"; }
cell p1:2147483101:0 p1i:2147483102:0::int8 p1e:2147483103:0::every_row
python3 - <<'P' || { echo "== the sound run's token gap is not under 1: stopping"; exit 7; }
import json, sys
rec = json.load(open("chiprun_out/pr56/p1.json"))
gap = dict((n, v) for n, v, *_ in rec["compared"])["token_gap_mean_spacings"]
print("== p1 token_gap_mean_spacings", gap)
sys.exit(0 if gap < 1.0 else 1)
P
cell s2:2147483104:0 s3:2147483105:0 i2:2147483109:0::int8 i3:2147483110:0::int8 t1:2147483111:1
