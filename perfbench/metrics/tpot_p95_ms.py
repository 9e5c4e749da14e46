"""95th percentile over the window's requests of (last token - first
token) / (tokens - 1), client clock: the per-answer token gap."""

from perfbench.lib.requests import tpots_ms
from perfbench.lib.stats import percentile


def read(run):
    if not run.get("window_rows"):
        return None
    return percentile(tpots_ms(run), 95)
