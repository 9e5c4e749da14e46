"""Tokens one held expert gets in a decode step, mean over the window's
steps and over the held experts of every expert layer (`expert_assignments`
on `engine.step` / held experts). The deployment's load is 2: a four-chip
batch of 64 x 8 experts a token / 256 experts."""

from perfbench.lib import hybrid_counts


def read(run):
    got = [a["expert_assignments"]
           for a in hybrid_counts.step_args(run, "expert_assignments")]
    if not got:
        return None
    return sum(got) / len(got) / hybrid_counts.held_expert_slots(run["config"])
