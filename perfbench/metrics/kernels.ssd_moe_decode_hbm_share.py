"""Bytes one WHOLE decode step of the Granite model must move
(`lib.granite_counts.decode_step_bytes`: mixers, shared MLPs, routers and
the head slice once, the weights of the experts the step TOUCHED once (the
program's counter, not the 36 held), the busy slots' state and tails read
and written, live K/V rows read; the counters are the program's own on the
`engine.step` spans of the traced seconds) / the chip's HBM bandwidth / the
step program's median device time in the trace (the SLOWEST bucket's)."""

from perfbench.lib import granite_counts
from perfbench.lib.peaks import peaks


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    step_ms = [v for k, v in tr["module_ms_p50"].items()
               if k.endswith("jit_decode_step")]
    args = granite_counts.step_args(run, run["traffic"]["trace_window_s"])
    if not step_ms or not args:
        return None
    mean = lambda key: sum(a[key] for a in args) / len(args)
    need = granite_counts.decode_step_bytes(
        run["config"], mean("state_slots"), mean("kv_rows"), mean("experts_touched"))
    bw = peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / (max(step_ms) / 1e3)
