"""The `ssd_scan` Pallas kernel's share of its roofline over the traced
seconds. As built the memory binds it, not the matrix unit: it reads x and
writes y in float32 (64 KB a position and layer) for 8.45 MFLOP of matrix
products a position (`lib.granite_counts.scan_kernel_flops`), 105 operations
a byte against the chip's ridge of 240, so the least time is the bytes'.
The bytes its calls have to move (`scan_kernel_bytes`, from each call's own
shape in the trace) are counted for the TRUE positions only: the padding of
a prompt bucket is taken out by the share of true tokens in the prompt
passes of those seconds (`tokens` of bucket x batch on the program's own
`engine.prefill_dispatch` spans) / the chip's HBM bandwidth / the calls'
summed device time."""

from perfbench.lib import granite_counts
from perfbench.lib.peaks import peaks


def read(run):
    calls = ((run.get("trace") or {}).get("kernel_calls") or {}).get("ssd_scan")
    if not calls:
        return None
    passes = granite_counts.prefill_spans(run, run["traffic"]["trace_window_s"])
    padded = sum(a["bucket"] * a["batch"] for a in passes)
    if not padded:
        return None
    true = sum(a["tokens"] for a in passes) / padded
    need = sum(granite_counts.scan_kernel_bytes(run["config"], b, s * true)
               for b, s, _ in calls)
    seconds = sum(t for _, _, t in calls)
    bw = peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / seconds if seconds else None
