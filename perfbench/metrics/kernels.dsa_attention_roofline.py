"""The `dsa_attention` Pallas kernel's share of the MXU's peak over the
traced seconds: per device event, from the call's own shape (n positions),
q . k and p . v over the CHOSEN rows only, 4 x 32 heads x 128 lanes for
each of the min(t + 1, 2048) rows of every query t
(`lib.keye_counts.attention_flops`) / the chip's bf16 peak / the events'
summed device time. The kernel walks every causal block under a mask (and
scores the block's indexer keys again): what it multiplies beyond the chosen
rows is not counted, so the share falls with the prompt's length."""

from perfbench.lib import keye_counts
from perfbench.lib.peaks import peaks


def read(run):
    calls = keye_counts.kernel_calls(run, "dsa_attention")
    seconds = sum(t for _, t in calls or [])
    if not seconds:
        return None
    need = sum(keye_counts.attention_flops(run["config"], n) for n, _ in calls)
    return 100.0 * need / peaks(run["device"]["kind"])["bf16_flops_per_s"] / seconds
