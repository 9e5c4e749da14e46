"""Median duration of the engine steps that only advanced slots, in the
EvaByte cell: the program's own `engine.step` spans that carry `window_rows`
(the EVA cache's argument: the busy slots' live rows of the window region)
and dispatched no prompt pass. A span is one call of the stepper: it
dispatches the next decode step and waits for the previous one's tokens, so
in steady state it lasts the device's step less what the loop spends between
two calls (handing tokens to the streams)."""

from perfbench.lib.program_spans import window
from perfbench.lib.stats import percentile


def read(run):
    steps = [s for s in (window(run) or {}).get("steps", [])
             if "window_rows" in s.get("args", {})
             and not s["args"].get("prefill_batches")]
    if not steps:
        return None
    return percentile([s["dur"] / 1e3 for s in steps], 50)
