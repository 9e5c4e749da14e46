"""Median time from the client's send to the replica's method being
entered: HTTP, proxy, router, replica queue (wall clocks of one host)."""

from perfbench.lib.stats import percentile


def read(run):
    entries = run.get("replica", {}).get("entries", {})
    ms = [1e3 * (entries[str(r["i"])] - r["sent_wall"])
          for r in run.get("window_rows", []) if str(r["i"]) in entries]
    return percentile(ms, 50) if ms else None
