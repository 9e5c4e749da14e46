"""Share of the held experts (summed over the expert layers) that got at
least one token in a decode step, mean over the window's steps: the
program's own counter `experts_touched` on `engine.step`."""

from perfbench.lib import hybrid_counts


def read(run):
    got = [a["experts_touched"] for a in hybrid_counts.step_args(run, "experts_touched")]
    if not got:
        return None
    return 100.0 * sum(got) / len(got) / hybrid_counts.held_expert_slots(run["config"])
