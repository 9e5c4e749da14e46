"""95th percentile over the window's requests of (last token - first
token) / (tokens - 1), client clock: the per-answer token gap's TAIL. It was
the end-to-end `tpot_p95_ms` up to PR 52; per layer since, beside the median
that took its place (`tpot_p50_ms`): at a step of 5 ms the 14 slowest of
275 answers are the short ones that met two or three prompt passes or the
replica's one stall a minute, and runs of one code spread 2-5% (PERF.md
sections 2 and 6)."""

from perfbench.lib.requests import tpots_ms
from perfbench.lib.stats import percentile


def read(run):
    if not run.get("window_rows"):
        return None
    return percentile(tpots_ms(run), 95)
