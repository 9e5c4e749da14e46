"""The `ssd_step` Pallas kernel's share of its HBM roofline over the traced
seconds: events x the bytes one call has to move at the mean number of busy
slots (`lib.granite_counts.step_kernel_bytes`: the busy slots' state, 4.19
MB a slot and layer, read and written once, the decay and dt x rows, B and C
read, y written; the slots are the program's own `state_slots` on the
`engine.step` spans of those seconds) / the chip's HBM bandwidth / the
events' summed device time. The kernel also spends an empty grid step on
every idle slot's channel blocks, which the bytes do not count."""

from perfbench.lib import granite_counts
from perfbench.lib.peaks import peaks


def read(run):
    events, seconds = ((run.get("trace") or {}).get("kernel_calls") or {}).get(
        "ssd_step") or (0, 0.0)
    args = granite_counts.step_args(run, run["traffic"]["trace_window_s"]) \
        if events else []
    if not seconds or not args:
        return None
    busy = sum(a["state_slots"] for a in args) / len(args)
    need = events * granite_counts.step_kernel_bytes(run["config"], busy)
    bw = peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / seconds
