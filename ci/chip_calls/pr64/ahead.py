"""One run of a cell (`perfbench/run.py`'s arguments, from the root of the
tree to run), then, from the timeline the run's parent still holds, what the
program recorded of PR 64's mechanism: the `programs.ahead` span of the
replica's engine and its `xla.compile` spans by thread, those marked `ahead`
apart. A tree without the mechanism prints zeros and no span.

    python3 <this file> --workload kimi-linear-serve-longgen --seed 7 --seconds 51 --trace 1
"""

import collections
import json
import os
import sys

sys.path.insert(0, os.getcwd())
sys.argv[0] = os.path.join(os.getcwd(), "perfbench", "run.py")

from perfbench import run  # noqa: E402

rc = run.main()

from ray_tpu.core import api  # noqa: E402

events = [e for e in api.timeline() if e.get("ph") == "X"]
said = [e for e in events if e["name"] == "programs.ahead"]
compiles = [e for e in events if e["name"] == "xla.compile"]
by_thread = collections.Counter(
    (e["pid"], e["tid"], e["args"].get("event", "")[:7], bool(e["args"].get("ahead")))
    for e in compiles)
reads = [e for e in compiles if e["args"].get("event") == "backend_compile_duration"]
ahead_reads = [e for e in reads if e["args"].get("ahead")]
create = [e for e in events if e["name"].startswith("actor.create::")]
print("[ahead] " + json.dumps({
    "programs.ahead": [dict(e["args"], pid=e["pid"], tid=e["tid"], dur_s=e["dur"] / 1e6)
                       for e in said],
    "actor.create_s": [round(e["dur"] / 1e6, 2) for e in create],
    "compile_spans_by_pid_tid_event_ahead": sorted(
        [list(k) + [n] for k, n in by_thread.items()]),
    "reads": len(reads), "reads_ahead": len(ahead_reads),
    "reads_missed": sum(e["args"].get("cache") == "miss" for e in reads),
    "read_s": round(sum(e["args"].get("retrieval_us", 0) for e in reads) / 1e6, 2),
    "read_s_ahead": round(sum(e["args"].get("retrieval_us", 0) for e in ahead_reads) / 1e6, 2),
    "trace_lower_s_ahead": round(sum(
        e["dur"] for e in compiles if e["args"].get("ahead")
        and e["args"].get("event") != "backend_compile_duration") / 1e6, 2),
}), flush=True)
sys.exit(rc)
