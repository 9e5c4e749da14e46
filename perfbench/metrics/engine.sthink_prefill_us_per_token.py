"""What a prompt token costs the engine's driver in the SmallThinker cell:
the summed duration of the window's `engine.prefill_dispatch` spans of a
stepper whose steps carry `wrapped_slots` / their summed `tokens` (the TRUE
tokens of each pass, the program's own argument; prompts inside one window
and walked ones alike). The span covers the dispatch of the pass and of the
write of its rows; the device's part shows where the device is the bound
(the next step's wait)."""

from perfbench.lib import sthink_counts


def read(run):
    if not sthink_counts.step_args(run):
        return None
    spans = sthink_counts.prefill_dispatches(run)
    tokens = sum(e["args"]["tokens"] for e in spans)
    return sum(e["dur"] for e in spans) / tokens if tokens else None
