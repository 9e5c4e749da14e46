"""Median duration of the engine steps that only advanced slot state, in the
Granite cell: the program's own `engine.step` spans that carry
`experts_touched` beside `state_slots` > 0 (the runs cache's arguments and
the scanned expert layers' counter: no other cell's steps have both) and
dispatched no prompt pass. A span is one call of the stepper: it dispatches
the next decode step and waits for the previous one's tokens, so in steady
state it lasts the device's step less what the loop spends between two
calls."""

from perfbench.lib.program_spans import window
from perfbench.lib.stats import percentile


def read(run):
    steps = [s for s in (window(run) or {}).get("steps", [])
             if s.get("args", {}).get("state_slots", 0) > 0
             and {"experts_touched", "kv_rows"} <= set(s["args"])
             and not s["args"].get("prefill_batches")]
    if not steps:
        return None
    return percentile([s["dur"] / 1e3 for s in steps], 50)
