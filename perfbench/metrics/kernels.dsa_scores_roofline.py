"""The `dsa_scores` Pallas kernel's share of its HBM roofline over the traced
seconds (its bound is bytes: 16 heads x 64 lanes x 2 operations ride a key of
256 B): events x the live indexer keys one call has to read at the mean
`index_rows` of the traced seconds' steps (the program's own counter) x 256 B
/ the chip's HBM bandwidth / the events' summed device time."""

from perfbench.lib import keye_counts
from perfbench.lib.peaks import peaks


def read(run):
    events, seconds = keye_counts.kernel_calls(run, "dsa_scores") or (0, 0.0)
    args = keye_counts.step_args(run, run["traffic"]["trace_window_s"]) \
        if events else []
    if not seconds or not args:
        return None
    rows = sum(a["index_rows"] for a in args) / len(args)
    need = events * rows * keye_counts.key_bytes(run["config"])
    return 100.0 * need / peaks(run["device"]["kind"])["hbm_bytes_per_s"] / seconds
