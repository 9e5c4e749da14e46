"""Share of the prediction module's drafts that the main model's own choice
confirmed, over the window's steps: `draft_accepted` / `draft_proposed` on
`engine.step` (device-side counters, of the step reaped in that span). With
seeded random weights the module agrees with the model about once in a
vocabulary, so this reads ~0: the cell prices speculation at its worst."""

from perfbench.lib.hybrid_counts import step_args


def read(run):
    args = step_args(run, "draft_proposed")
    proposed = sum(a["draft_proposed"] for a in args)
    if not proposed:
        return None
    return 100.0 * sum(a["draft_accepted"] for a in args) / proposed
