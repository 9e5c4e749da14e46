"""Median over the window's requests of (last token - first token) /
(tokens - 1), client clock: the per-answer token gap a user of the chat
cell typically sees. End to end since PR 52 in the place of its tail
(`serve.tpot_p95_ms`, per layer since): one stall of the replica or a few
short answers that met three prompt passes move the 14 slowest of 275
answers by 2-5% from run to run and leave the median within 0.3% (PERF.md
sections 2 and 6)."""

from perfbench.lib.requests import tpots_ms
from perfbench.lib.stats import percentile


def read(run):
    if not run.get("window_rows"):
        return None
    return percentile(tpots_ms(run), 50)
