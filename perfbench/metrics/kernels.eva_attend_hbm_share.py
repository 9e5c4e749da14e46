"""The `eva_decode_attention` Pallas kernel's share of its HBM roofline over
the traced seconds: events x the bytes one call has to move at the mean live
rows (`lib.eva_counts.attend_kernel_bytes`: K and V of the busy slots' live
window rows and visible summaries, one layer; the rows are the program's own
`window_rows` + `summary_rows` on the `engine.step` spans of those seconds) /
the chip's HBM bandwidth / the events' summed device time. The kernel moves
whole blocks of 256 rows, so what it reads past a region's last live row is
not counted as useful; every head has keys of its own, so 4 x 128 operations
ride every 512 bytes and the bound is the bytes'."""

from perfbench.lib import eva_counts
from perfbench.lib.hybrid_counts import step_args
from perfbench.lib.peaks import peaks


def read(run):
    events, seconds = ((run.get("trace") or {}).get("kernel_calls") or {}).get(
        "eva_decode_attention") or (0, 0.0)
    args = step_args(run, "window_rows", run["traffic"]["trace_window_s"]) \
        if events else []
    if not seconds or not args:
        return None
    mean = lambda key: sum(a[key] for a in args) / len(args)
    need = events * eva_counts.attend_kernel_bytes(
        run["config"], mean("window_rows"), mean("summary_rows"))
    bw = peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / seconds
