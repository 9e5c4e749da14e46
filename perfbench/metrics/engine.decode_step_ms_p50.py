"""Median duration of the engine's `step()` calls in the window that
ran no prefill: with one step of lookahead that is one decode step."""

from perfbench.lib.stats import percentile


def read(run):
    spans = run.get("replica", {}).get("spans", {})
    t0, t1 = run["t_open"], run["t_open"] + run["seconds"]
    prefills = sorted(a for a, _, _ in spans.get("bench.prefill", []))
    ms = []
    for a, b, _ in spans.get("bench.engine_step", []):
        if a < t0 or b > t1 or any(a <= p <= b for p in prefills):
            continue
        ms.append(1e3 * (b - a))
    return percentile(ms, 50) if ms else None
