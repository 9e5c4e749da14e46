"""Share of the window's engine steps that dispatched a prefill
(`engine.step` spans whose `prefill_batches` is above 0): such a step puts
a prompt pass between two decode steps of every running answer."""

from perfbench.lib.program_spans import window


def read(run):
    w = window(run)
    if not w or not w["steps"]:
        return None
    with_prefill = sum(1 for s in w["steps"]
                       if (s.get("args") or {}).get("prefill_batches", 0) > 0)
    return 100.0 * with_prefill / len(w["steps"])
