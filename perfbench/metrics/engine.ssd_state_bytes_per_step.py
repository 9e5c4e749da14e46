"""Bytes of recurrent state one decode step of the Granite model needs, mean
over the window's steps: busy slots (`state_slots` on `engine.step`, the
program's own) x 9 Mamba-2 layers x (S, 4.19 MB float32, and the convolution
tail), read AND written, priced by `lib.granite_counts`. The `ssd_step`
kernel moves just that; an XLA step would move all 32 slots' state."""

from perfbench.lib import granite_counts


def read(run):
    got = [granite_counts.state_bytes_per_step(run["config"], a["state_slots"])
           for a in granite_counts.step_args(run)]
    return sum(got) / len(got) if got else None
