"""The `dsa_rows` Pallas kernel's share of its HBM roofline over the traced
seconds (its bound is bytes: a chosen position's 2,048 B serve 32 heads x 128
lanes x 4 operations): events x the chosen positions one call has to read at
the mean `selected_rows` of the traced seconds' steps (the program's own
counter) x 2,048 B / the chip's HBM bandwidth / the events' summed device
time. The kernel fetches a position a DMA: what it pays is descriptors, not
bytes, and the share says how far that is from the bytes' own time."""

from perfbench.lib import keye_counts
from perfbench.lib.peaks import peaks


def read(run):
    events, seconds = keye_counts.kernel_calls(run, "dsa_rows") or (0, 0.0)
    args = keye_counts.step_args(run, run["traffic"]["trace_window_s"]) \
        if events else []
    if not seconds or not args:
        return None
    rows = sum(a["selected_rows"] for a in args) / len(args)
    need = events * rows * keye_counts.kv_position_bytes(run["config"])
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / seconds
