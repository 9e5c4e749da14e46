"""Bytes of per-slot state one decode step of the Jamba model needs, mean
over the window's steps: SSM state and convolution tail read and written for
`state_slots` slots, plus `kv_rows` live K/V rows of the two attention
layers read (the program's counters on `engine.step`, priced by
`lib.jamba_counts`). The step program moves the state of ALL slots whatever
is busy; this is what it would have to."""

from perfbench.lib import jamba_counts
from perfbench.lib.hybrid_counts import step_args


def read(run):
    got = [jamba_counts.state_bytes_per_step(run["config"], a["state_slots"],
                                             a["kv_rows"])
           for a in step_args(run, "kv_rows")]
    return sum(got) / len(got) if got else None
