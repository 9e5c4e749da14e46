"""Median time to first token over the window's requests: the arrival
phase against the engine's step clock, noisy by nature (PERF.md)."""

from perfbench.lib.requests import ttfts_ms
from perfbench.lib.stats import percentile


def read(run):
    if not run.get("window_rows"):
        return None
    return percentile(ttfts_ms(run), 50)
