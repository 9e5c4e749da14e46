"""Share of the held experts (all 64 a layer, 768 over the twelve layers)
that got at least one token in a decode step of the SmallThinker cell, mean
over the window's steps: the program's own counter `experts_touched` on
`engine.step`. It prices the step's one read that the traffic moves: a
touched expert is 11.8 MB, and with every expert held a step of b busy
slots touches about 64 x (1 - (58 / 64)^b) a layer."""

from perfbench.lib import sthink_counts


def read(run):
    got = [a["experts_touched"] for a in sthink_counts.step_args(run)
           if "experts_touched" in a]
    if not got:
        return None
    return 100.0 * sum(got) / len(got) / sthink_counts.held_expert_slots(run["config"])
