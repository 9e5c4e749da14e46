"""The banded prompt kernel's (`flash_attention_banded`) share of the MXU's
peak over the prompt passes that ran WHOLE inside the traced seconds: q . k
and p . v over the (query, key) PAIRS the equations name (a window layer
min(t + 1, 4096) keys a query, a full layer t + 1; 4 x 128 heads x 128
lanes a pair: `lib.cmda_counts.attention_flops`) of those passes' TRUE
tokens / the chip's bf16 peak / the device time of the kernel's events that
ran UNDER those passes' steps (`lib.cmda_counts.pass_steps`: the program's
`engine.step` spans that dispatched a pass and began and ended there; the
events by their start on the wall clock). A step counts only if each of its
spans holds ONE prompt (the span of several holds the sum of their tokens,
which gives neither their pairs nor their calls) and the trace holds every
call its passes make (one a layer and window walked), so a pass that
straddles an edge of the trace counts neither as work nor as time.
What the kernel multiplies beyond the pairs (the masked part of a block on
the band's edges, a last chunk's padding) counts as time only."""

from perfbench.lib import cmda_counts
from perfbench.lib.peaks import peaks


def read(run):
    events = cmda_counts.prompt_kernel_events(run)
    steps = cmda_counts.pass_steps(run, run["traffic"]["trace_window_s"]) \
        if events else []
    c, need, seconds = run["config"], 0.0, 0.0
    for start, end, passes in steps:
        under = [s for t, s in events if start <= t < end]
        if all(a["batch"] == 1 for a in passes) and len(under) == sum(
                cmda_counts.pass_kernel_calls(c, a) for a in passes):
            need += sum(cmda_counts.attention_flops(c, a["tokens"]) for a in passes)
            seconds += sum(under)
    if not seconds:
        return None
    return 100.0 * need / peaks(run["device"]["kind"])["bf16_flops_per_s"] / seconds
