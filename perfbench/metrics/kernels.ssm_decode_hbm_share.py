"""Bytes one decode step of the Jamba model must move (`lib.jamba_counts`:
every weight once, SSM state and convolution tails of the busy slots read
and written, live K/V rows read; the counters are the program's own on the
`engine.step` spans of the traced seconds) / the chip's HBM bandwidth / the
step program's median device time in the trace (the SLOWEST bucket's)."""

from perfbench.lib import jamba_counts
from perfbench.lib.hybrid_counts import step_args
from perfbench.lib.peaks import peaks


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    step_ms = [v for k, v in tr["module_ms_p50"].items()
               if k.endswith("jit_decode_step")]
    args = step_args(run, "kv_rows", run["traffic"]["trace_window_s"])
    if not step_ms or not args:
        return None
    mean = lambda key: sum(a[key] for a in args) / len(args)
    need = jamba_counts.decode_step_bytes(run["config"], mean("state_slots"),
                                          mean("kv_rows"))
    bw = peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / (max(step_ms) / 1e3)
