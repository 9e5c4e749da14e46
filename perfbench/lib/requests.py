"""Per-request times of a serving run's window, from the client's rows."""

from __future__ import annotations

from typing import List


def ttfts_ms(run) -> List[float]:
    """First streamed token received - the time the request was DUE; a
    request that failed or was refused counts as the window's length."""
    return [1e3 * ((r["arrivals_s"][0] - r["due_s"]) if r["ok"] else run["seconds"])
            for r in run["window_rows"]]


def tpots_ms(run) -> List[float]:
    """(last token - first token) / (tokens - 1) per answer of two tokens or
    more; a failed request counts as the window's length."""
    return [1e3 * ((r["arrivals_s"][-1] - r["arrivals_s"][0]) / (r["n_tokens"] - 1)
                   if r["ok"] else run["seconds"])
            for r in run["window_rows"] if r["max_new_tokens"] > 1]
