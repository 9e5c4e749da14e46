"""95th percentile, over the window's requests, of (first streamed token
received by the client - the time the request was DUE). A request that
failed or was refused counts as the window's length. Per-layer since PR 25:
its runs spread too widely for any bound the contract allows (PERF.md)."""

from perfbench.lib.requests import ttfts_ms
from perfbench.lib.stats import percentile


def read(run):
    if not run.get("window_rows"):
        return None
    return percentile(ttfts_ms(run), 95)
