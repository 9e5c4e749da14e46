"""Share of the traced window in which no operation ran on the device."""

from perfbench.lib.xplane import idle_share_pct


def read(run):
    if not run.get("trace") or "step_end_s" not in run:
        return None
    return idle_share_pct(run["trace"])
