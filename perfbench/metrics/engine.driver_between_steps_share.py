"""Share of the engine driver thread's working time D that passed BETWEEN
two steps: the window's `engine.between_steps` spans less their `slept_us`
(the sleep with nothing to do) over D (`lib/token_path.py`)."""

from perfbench.lib.token_path import driver_share


def read(run):
    return driver_share(run, "between_us")
