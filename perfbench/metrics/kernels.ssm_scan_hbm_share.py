"""The `selective_scan` Pallas kernel's share of its HBM roofline over the
traced seconds: the bytes its calls have to move (`lib.jamba_counts.
scan_kernel_bytes`, from each call's own shape in the trace: u and dt read,
y written, B and C read, the state read and written once, all float32) /
the chip's HBM bandwidth / the calls' summed device time. The kernel is
bound by the vector unit (an exp and five multiply-adds per state element
and position), not by the bytes, so a low share is expected."""

from perfbench.lib import jamba_counts
from perfbench.lib.peaks import peaks


def read(run):
    calls = ((run.get("trace") or {}).get("kernel_calls") or {}).get("selective_scan")
    if not calls:
        return None
    need = sum(jamba_counts.scan_kernel_bytes(run["config"], b, s) for b, s, _ in calls)
    seconds = sum(t for _, _, t in calls)
    bw = peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / seconds if seconds else None
