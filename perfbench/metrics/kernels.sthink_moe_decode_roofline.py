"""The decode step's share of its HBM roofline in the SmallThinker cell,
which its expert layers set: bytes one WHOLE step must move
(`lib.sthink_counts.decode_step_bytes`: attention, routers and the head
once, 1.28 GB; the weights of the experts the step TOUCHED once, 11.8 MB
each; the rows of ring and full caches; the counters are the program's own
on the `engine.step` spans of the traced seconds) / the chip's HBM bandwidth
/ the step program's device time in the trace: the MEDIAN step's bytes over
the median step's time (the time grows with the bytes, so the two medians
are of one step; the mean step's bytes over the median's time would read a
few crowded steps' experts against a quiet step's time, and could pass
100%: `kernels.swa_moe_decode_roofline` argues the same)."""

import statistics

from perfbench.lib import sthink_counts
from perfbench.lib.peaks import peaks


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    step_ms = [v for k, v in tr["module_ms_p50"].items()
               if k.endswith("jit_decode_step")]
    args = [a for a in sthink_counts.step_args(run, run["traffic"]["trace_window_s"])
            if "experts_touched" in a]
    if not step_ms or not args:
        return None
    need = statistics.median(sthink_counts.decode_step_bytes(
        run["config"], a["window_rows"], a["full_rows"], a["experts_touched"])
        for a in args)
    bw = peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / (max(step_ms) / 1e3)
