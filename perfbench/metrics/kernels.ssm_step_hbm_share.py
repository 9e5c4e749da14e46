"""The `selective_step` Pallas kernel's share of its HBM roofline over the
traced seconds: events x the bytes one call has to move at the mean number of
busy slots (`lib.jamba_counts.step_kernel_bytes`: the busy slots' state read
and written once, dt, u, B, C read, y written; the slots are the program's
own `state_slots` on the `engine.step` spans of those seconds) / the chip's
HBM bandwidth / the events' summed device time. The kernel also spends ~0.25
us a layer on every idle slot's empty grid step, which the bytes do not count."""

from perfbench.lib import jamba_counts
from perfbench.lib.hybrid_counts import step_args
from perfbench.lib.peaks import peaks


def read(run):
    events, seconds = ((run.get("trace") or {}).get("kernel_calls") or {}).get(
        "selective_step") or (0, 0.0)
    args = step_args(run, "kv_rows", run["traffic"]["trace_window_s"]) if events else []
    if not seconds or not args:
        return None
    busy = sum(a["state_slots"] for a in args) / len(args)
    need = events * jamba_counts.step_kernel_bytes(run["config"], busy)
    bw = peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / seconds
