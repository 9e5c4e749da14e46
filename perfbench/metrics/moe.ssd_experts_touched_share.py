"""Share of the held experts (36 a layer, summed over the ten layers) that
got at least one token in a decode step of the Granite cell, mean over the
window's steps: the program's own counter `experts_touched` on
`engine.step`. It is the share of the held experts' 6.8 GB a step reads."""

from perfbench.lib import granite_counts


def read(run):
    got = [a["experts_touched"] for a in granite_counts.step_args(run)]
    if not got:
        return None
    return 100.0 * sum(got) / len(got) / granite_counts.held_expert_slots(run["config"])
