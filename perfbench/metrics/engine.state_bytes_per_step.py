"""Bytes of per-slot state one decode step moves, mean over the window's
steps: KDA state and convolution tail read and written for `state_slots`
slots, plus `latent_rows` live latent rows read (the program's counters on
`engine.step`, priced by `lib.hybrid_counts`)."""

from perfbench.lib import hybrid_counts


def read(run):
    got = [hybrid_counts.state_bytes_per_step(run["config"], a["state_slots"],
                                              a["latent_rows"])
           for a in hybrid_counts.step_args(run, "state_slots")]
    return sum(got) / len(got) if got else None
