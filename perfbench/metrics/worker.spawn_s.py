"""From the raylet's `Popen` of the chip holder to its registration handled:
the program's `worker.spawn` span, the raylet's clock on both ends."""

from perfbench.lib.setup_spans import stage_s


def read(run):
    return stage_s(run, "worker.spawn")
