"""What of its contexts a decode step of the Command A+ model reads: the
rows the layers read (3 window layers x `window_rows`, min(n, 4096) a slot,
+ 1 full layer x `full_rows`, n a slot) / the rows a stack of full layers
would read (4 x `full_rows`), summed over the window's steps; the program's
own counters on `engine.step`. 100 would be no window; it falls as the busy
slots' contexts grow past 4,096 (towards 1 / 4 + 3 x 4096 / 4 n)."""

from perfbench.lib import cmda_counts


def read(run):
    c, args = run["config"], cmda_counts.step_args(run)
    layers = sum(cmda_counts.layer_kinds(c)) if args else 0
    full = layers * sum(a["full_rows"] for a in args)
    read_ = sum(cmda_counts.rows_per_step(c, a["window_rows"], a["full_rows"])
                for a in args)
    return 100.0 * read_ / full if full else None
