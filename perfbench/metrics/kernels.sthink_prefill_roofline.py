"""The banded prompt kernel's (`flash_attention_banded`, 7 query heads a key
head) share of the MXU's peak over the prompt passes that ran WHOLE inside
the traced seconds of the SmallThinker cell: q . k and p . v over the
(query, key) PAIRS the equations name (a window layer min(t + 1, 4096) keys
a query, a global layer t + 1; 4 x 28 heads x 128 lanes a pair:
`lib.sthink_counts.attention_flops`) of those passes' TRUE tokens / the
chip's bf16 peak / the device time of the kernel's events that ran UNDER
those passes' steps (`lib.cmda_counts.pass_steps`: the program's
`engine.step` spans that dispatched a pass and began and ended there; the
events by their start on the wall clock). A step counts only if each of its
spans holds ONE prompt and the trace holds every call its passes make (one a
layer and window walked), so a pass that straddles an edge of the trace
counts neither as work nor as time. What the kernel multiplies beyond the
pairs (the masked part of a block on the band's edges, a last chunk's
padding) counts as time only."""

from perfbench.lib import sthink_counts
from perfbench.lib.peaks import peaks


def read(run):
    if not sthink_counts.step_args(run):
        return None
    events = sthink_counts.prompt_kernel_events(run)
    steps = sthink_counts.pass_steps(run, run["traffic"]["trace_window_s"]) \
        if events else []
    c, need, seconds = run["config"], 0.0, 0.0
    for start, end, passes in steps:
        under = [s for t, s in events if start <= t < end]
        if all(a["batch"] == 1 for a in passes) and len(under) == sum(
                sthink_counts.pass_kernel_calls(c, a) for a in passes):
            need += sum(sthink_counts.attention_flops(c, a["tokens"]) for a in passes)
            seconds += sum(under)
    if not seconds:
        return None
    return 100.0 * need / peaks(run["device"]["kind"])["bf16_flops_per_s"] / seconds
