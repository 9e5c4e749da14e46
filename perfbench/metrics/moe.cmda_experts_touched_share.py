"""Share of the held experts (16 a layer, 64 over the four layers) that got
at least one token in a decode step of the Command A+ cell, mean over the
window's steps: the program's own counter `experts_touched` on
`engine.step`. It prices the step's one read that the traffic moves: a
touched expert is 100.7 MB."""

from perfbench.lib import cmda_counts


def read(run):
    got = [a["experts_touched"] for a in cmda_counts.step_args(run)
           if "experts_touched" in a]
    if not got:
        return None
    return 100.0 * sum(got) / len(got) / cmda_counts.held_expert_slots(run["config"])
