"""Bytes of the slot tables one decode step of the EvaByte model reads, mean
over the window's steps: the busy slots' live rows of the window region
(`window_rows`) and their visible summaries (`summary_rows`), K and V, every
layer (the program's counters on `engine.step`, priced by `lib.eva_counts`).
Plain attention over the same positions would read 16 rows where this reads
the summaries' one."""

from perfbench.lib import eva_counts
from perfbench.lib.hybrid_counts import step_args


def read(run):
    got = [eva_counts.cache_bytes_per_step(run["config"], a["window_rows"],
                                           a["summary_rows"])
           for a in step_args(run, "window_rows")]
    return sum(got) / len(got) if got else None
