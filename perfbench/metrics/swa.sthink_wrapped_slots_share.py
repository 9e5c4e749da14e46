"""Share of the busy slots whose ring has wrapped (context at or past the
window's 4,096 positions: the row at n mod W leaves as the step's own enters
and a global layer reads more rows than a window layer), summed over the
window's steps: `wrapped_slots` / `active`, the program's own counters on
`engine.step`. A descriptor of the traffic, as `swa.window_rows_share` is:
0 would be a cell whose window never binds."""

from perfbench.lib import sthink_counts


def read(run):
    args = [a for a in sthink_counts.step_args(run) if a.get("active")]
    busy = sum(a["active"] for a in args)
    return 100.0 * sum(a["wrapped_slots"] for a in args) / busy if busy else None
