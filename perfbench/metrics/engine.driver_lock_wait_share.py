"""Share of the engine driver thread's working time D spent waiting to
ENTER the engine's bookkeeping lock inside its steps: the `lock_wait_us`
counters of the window's `engine.step` spans over D (`lib/token_path.py`).
The streaming threads a reap wakes take that lock in turn."""

from perfbench.lib.token_path import driver_share


def read(run):
    return driver_share(run, "lock_wait_us")
