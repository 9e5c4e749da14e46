"""Median time of one optimizer step in the window, host clock."""

from perfbench.lib.stats import percentile


def read(run):
    ends = run.get("step_end_s")
    if not ends:
        return None
    return percentile([1e3 * (b - a) for a, b in zip([0.0] + ends, ends)], 50)
