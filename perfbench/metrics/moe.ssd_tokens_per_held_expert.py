"""Tokens one held expert gets in a decode step of the Granite cell, mean
over the window's steps and over the 36 held experts of each of the ten
layers (`expert_assignments` on `engine.step`, the program's own counter of
what landed on held experts / held experts). The deployment's load at equal
busy slots is twice this: its two chips' tokens meet in every expert
(2 x 10 / 72 = 0.28 tokens a held expert and busy slot)."""

from perfbench.lib import granite_counts


def read(run):
    got = [a["expert_assignments"] for a in granite_counts.step_args(run)]
    if not got:
        return None
    return sum(got) / len(got) / granite_counts.held_expert_slots(run["config"])
