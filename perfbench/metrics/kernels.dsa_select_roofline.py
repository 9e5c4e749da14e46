"""The `dsa_select` Pallas kernel's share of the MXU's peak over the traced
seconds: per device event, from the call's own shape (n positions), the
indexer's scores of every causal pair, 2 x 16 x 64 operations each
(`lib.keye_counts.select_flops`) / the chip's bf16 peak / the events' summed
device time. The scores are the only work the selection NEEDS; the kernel's
radix search for the 2,048th best (32 passes of compares over the scores, on
the vector unit) is how it is done, and is what bounds it: the share is low
by design and says what a cheaper search would buy."""

from perfbench.lib import keye_counts
from perfbench.lib.peaks import peaks


def read(run):
    calls = keye_counts.kernel_calls(run, "dsa_select")
    seconds = sum(t for _, t in calls or [])
    if not seconds:
        return None
    need = sum(keye_counts.select_flops(run["config"], n) for n, _ in calls)
    return 100.0 * need / peaks(run["device"]["kind"])["bf16_flops_per_s"] / seconds
