"""What of its context a decode step of the Keye model attends to:
`selected_rows` / `kv_rows` summed over the window's steps (the program's own
counters on `engine.step`: min(n, 2048) of each busy slot's n positions).
100 would be no sparsity."""

from perfbench.lib import keye_counts


def read(run):
    args = keye_counts.step_args(run)
    rows = sum(a["kv_rows"] for a in args)
    return 100.0 * sum(a["selected_rows"] for a in args) / rows if rows else None
