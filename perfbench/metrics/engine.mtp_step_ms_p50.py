"""Median duration of the engine steps that verified a draft and drafted
the next: the program's own `engine.step` spans with `draft_proposed` > 0
that dispatched no prompt pass. A span is one call of the stepper: it
dispatches the next step and waits for the previous one's tokens."""

from perfbench.lib.program_spans import window
from perfbench.lib.stats import percentile


def read(run):
    steps = [s for s in (window(run) or {}).get("steps", [])
             if s.get("args", {}).get("draft_proposed", 0) > 0
             and not s["args"].get("prefill_batches")]
    if not steps:
        return None
    return percentile([s["dur"] / 1e3 for s in steps], 50)
