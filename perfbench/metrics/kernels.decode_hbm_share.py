"""Bytes one decode step must read (weights once + the live rows of the
cache at the traced steps: `lib.counts`) / the chip's HBM bandwidth / the
step program's median device time in the trace."""

from perfbench.lib import counts
from perfbench.lib.peaks import peaks


def read(run):
    tr = run.get("trace")
    if not tr or "replica" not in run:
        return None
    step_ms = [v for k, v in tr["module_ms_p50"].items() if "decode_step_fused" in k]
    a, b = run["traffic"]["trace_window_s"]
    t0, t1 = run["t_open"] + a, run["t_open"] + b
    rows = [r for t, _, r in run["replica"]["steps"] if t0 <= t < t1]
    if not step_ms or not rows:
        return None
    need = counts.decode_step_bytes(run["config"], sum(rows) / len(rows))
    bw = peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / (max(step_ms) / 1e3)
