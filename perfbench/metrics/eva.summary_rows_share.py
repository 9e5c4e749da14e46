"""Of all the rows the window's decode steps read out of the slot tables,
the share that were chunk summaries (the program's `summary_rows` over
`window_rows` + `summary_rows` on `engine.step`, summed over the window):
0 while every slot stands in its first window, near a half at 16 closed
windows."""

from perfbench.lib.hybrid_counts import step_args


def read(run):
    args = step_args(run, "window_rows")
    rows = sum(a["window_rows"] + a["summary_rows"] for a in args)
    if not rows:
        return None
    return 100.0 * sum(a["summary_rows"] for a in args) / rows
