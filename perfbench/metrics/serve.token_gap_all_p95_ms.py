"""95th percentile of EVERY gap between two tokens of every answer in the
window: a prefill between two steps lengthens one gap of every running
answer, which the per-answer mean (`tpot_p50_ms`, `serve.tpot_p95_ms`) averages away."""

from perfbench.lib.stats import percentile


def read(run):
    gaps = [1e3 * (b - a) for r in run.get("window_rows", []) if r["ok"]
            for a, b in zip(r["arrivals_s"], r["arrivals_s"][1:])]
    return percentile(gaps, 95) if gaps else None
