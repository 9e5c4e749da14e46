"""How late the load generator sent: 95th percentile of (sent - due)."""

from perfbench.lib.stats import percentile


def read(run):
    rows = [r for r in run.get("window_rows", []) if "sent_s" in r]
    if not rows:
        return None
    return percentile([1e3 * (r["sent_s"] - r["due_s"]) for r in rows], 95)
