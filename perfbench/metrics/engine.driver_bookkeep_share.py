"""Share of the engine driver thread's working time D spent HOLDING the
engine's bookkeeping lock to hand a step's tokens to their requests (the
per-slot loop and `notify_all` in `_drain_pending_first` and `_reap`): the
`bookkeep_us` counters of the window's `engine.step` spans over D
(`lib/token_path.py`). Part of the driver's own work, beside its dispatches."""

from perfbench.lib.token_path import driver_share


def read(run):
    return driver_share(run, "bookkeep_us")
