"""The decode kernel's (`gqa_decode_attention`, over ring and full rows)
share of its HBM roofline over the traced seconds (its bound is bytes: a
row's 4,096 B serve 128 heads x 128 lanes x 4 operations): events x the
rows one call has to read at the traced steps' mean (3 x `window_rows` + 1 x
`full_rows`, the program's own counters, over the four layers' four calls)
x 4,096 B / the chip's HBM bandwidth / the events' summed device time. A
block past a slot's last row and the ring's one row that left the window
are read and masked: time, not bytes."""

from perfbench.lib import cmda_counts
from perfbench.lib.peaks import peaks


def read(run):
    events, seconds = cmda_counts.kernel_calls(run, "gqa_decode_attention") or (0, 0.0)
    args = cmda_counts.step_args(run, run["traffic"]["trace_window_s"]) \
        if events else []
    if not seconds or not args:
        return None
    c = run["config"]
    rows = sum(cmda_counts.rows_per_step(c, a["window_rows"], a["full_rows"])
               for a in args) / len(args) / sum(cmda_counts.layer_kinds(c))
    need = events * rows * cmda_counts.row_bytes(c)
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / seconds
