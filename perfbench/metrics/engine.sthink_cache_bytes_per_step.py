"""Bytes of its caches one decode step of the SmallThinker stage needs, mean
over the window's steps: (9 window layers x `window_rows` + 3 global layers
x `full_rows`) x a position's 2,048 B of k and v, the program's own counters
on `engine.step`, priced by `lib.sthink_counts`. A stack of global layers
would move 12 x `full_rows` x 2,048 B."""

from perfbench.lib import sthink_counts


def read(run):
    got = [sthink_counts.cache_bytes_per_step(run["config"], a["window_rows"],
                                              a["full_rows"])
           for a in sthink_counts.step_args(run)]
    return sum(got) / len(got) if got else None
