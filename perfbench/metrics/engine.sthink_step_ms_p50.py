"""Median duration of the engine steps that only decoded, in the
SmallThinker cell: the program's own `engine.step` spans that carry
`wrapped_slots` (this cell's program reports it beside `window_rows` and
`full_rows`) and dispatched no prompt pass. A span is one call of the
stepper: it dispatches the next decode step and waits for the previous
one's tokens."""

from perfbench.lib.program_spans import window
from perfbench.lib.stats import percentile


def read(run):
    steps = [s for s in (window(run) or {}).get("steps", [])
             if {"window_rows", "full_rows", "wrapped_slots"} <= set(s.get("args", {}))
             and not s["args"].get("prefill_batches")]
    if not steps:
        return None
    return percentile([s["dur"] / 1e3 for s in steps], 50)
