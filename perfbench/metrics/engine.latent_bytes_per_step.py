"""Bytes of latent rows one decode step has to read, mean over the window's
steps: the busy slots' live rows (`latent_rows` on `engine.step`, known on
the host at dispatch) x the stored row of every MLA layer, the prediction
module's with them (`lib.pangu_counts`)."""

from perfbench.lib import pangu_counts
from perfbench.lib.hybrid_counts import step_args


def read(run):
    got = [pangu_counts.latent_bytes_per_step(run["config"], a["latent_rows"])
           for a in step_args(run, "latent_rows") if "draft_proposed" in a]
    if not got:
        return None
    return sum(got) / len(got)
