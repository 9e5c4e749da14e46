"""Bytes of its caches one decode step of the Command A+ model needs, mean
over the window's steps: (3 window layers x `window_rows` + 1 full layer x
`full_rows`) x a position's 4,096 B of k and v, the program's own counters
on `engine.step`, priced by `lib.cmda_counts`. A stack of full layers would
move 4 x `full_rows` x 4,096 B."""

from perfbench.lib import cmda_counts


def read(run):
    got = [cmda_counts.cache_bytes_per_step(run["config"], a["window_rows"],
                                            a["full_rows"])
           for a in cmda_counts.step_args(run)]
    return sum(got) / len(got) if got else None
