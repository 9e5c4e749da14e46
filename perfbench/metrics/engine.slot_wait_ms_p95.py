"""95th percentile of the time a request waited because no cache slot was
free: from the first admission pass that left it waiting to the pass that
admitted it (0 for a request admitted by the first pass after its submit)."""

from perfbench.lib.stats import percentile


def read(run):
    reqs = [q for q in run.get("replica", {}).get("requests", [])
            if "admit" in q and "submit" in q and q["submit"] >= run["t_open"]]
    if not reqs:
        return None
    return percentile([1e3 * max(0.0, q["admit"] - q.get("denied", q["admit"]))
                       for q in reqs], 95)
