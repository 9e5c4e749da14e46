#!/bin/bash
# call 13 (1 chip, the last 4.4 chip-minutes): the chat cell traced with its
# `trace_window_s` cut from [3.0, 8.0] to [3.0, 4.5], from the repo's root,
# with whatever compile cache the machine comes with.
bash perfbench/tools/pr52/run_one.sh short_traced internlm2-serve-chat $((2147400000 + RANDOM)) 1
python3 perfbench/tools/pr52/tail.py .perfbench_out/internlm2-serve-chat/last_run.json
