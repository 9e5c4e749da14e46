"""Bytes one decode step of the EvaByte model must move (`lib.eva_counts`:
every layer's weights and the first prediction head's columns once, the busy
slots' live rows of both regions of the slot tables; the counters are the
program's own on the `engine.step` spans of the traced seconds) / the chip's
HBM bandwidth / the step program's median device time in the trace (the
SLOWEST bucket's): the whole step's share of the HBM roofline."""

from perfbench.lib import eva_counts
from perfbench.lib.hybrid_counts import step_args
from perfbench.lib.peaks import peaks


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    step_ms = [v for k, v in tr["module_ms_p50"].items()
               if k.endswith("jit_decode_step")]
    args = step_args(run, "window_rows", run["traffic"]["trace_window_s"])
    if not step_ms or not args:
        return None
    mean = lambda key: sum(a[key] for a in args) / len(args)
    need = eva_counts.decode_step_bytes(run["config"], mean("window_rows"),
                                        mean("summary_rows"))
    bw = peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / (max(step_ms) / 1e3)
