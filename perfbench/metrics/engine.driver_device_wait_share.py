"""Share of the engine driver thread's working time D spent waiting for the
device: the `engine.wait_device` spans inside the window's `engine.step`
spans over D (`lib/token_path.py`: steps + what passed between them, less
the sleep with no work). Near 100: the chip sets the pace; the rest is host."""

from perfbench.lib.token_path import driver_share


def read(run):
    return driver_share(run, "device_wait_us")
