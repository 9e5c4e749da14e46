"""Tokens one held expert gets in a decode step, mean over the window's
steps and over the held experts of every expert layer, the prediction
module's with them (`expert_assignments` on `engine.step` / held experts).
The deployment's load is 24 at 12 busy slots a chip: 32 chips' batches x 2
positions x 8 experts a token / 256 experts; one chip alone brings 1/32."""

from perfbench.lib import pangu_counts
from perfbench.lib.hybrid_counts import step_args


def read(run):
    got = [a["expert_assignments"] for a in step_args(run, "draft_proposed")]
    if not got:
        return None
    return sum(got) / len(got) / pangu_counts.held_expert_slots(run["config"])
