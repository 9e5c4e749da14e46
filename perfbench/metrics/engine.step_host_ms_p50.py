"""Median host time of an engine step in the window: the `engine.step`
span (admission, prefill and decode dispatch, bookkeeping) less the
`engine.wait_device` spans inside it."""

from perfbench.lib.program_spans import window
from perfbench.lib.stats import percentile


def read(run):
    w = window(run)
    if not w or not w["steps"]:
        return None
    return percentile([s["host_us"] / 1e3 for s in w["steps"]], 50)
