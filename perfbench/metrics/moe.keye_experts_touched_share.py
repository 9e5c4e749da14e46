"""Share of the held experts (128 a layer, 768 over the six layers) that got
at least one token in a decode step of the Keye cell, mean over the window's
steps: the program's own counter `experts_touched` on `engine.step`. It
prices the step's largest read: a touched expert is 9.44 MB."""

from perfbench.lib import keye_counts


def read(run):
    got = [a["experts_touched"] for a in keye_counts.step_args(run)
           if "experts_touched" in a]
    if not got:
        return None
    return 100.0 * sum(got) / len(got) / keye_counts.held_expert_slots(run["config"])
