"""From a TPU demand's arrival at the raylet to the `Popen` of the worker
that was spawned for its chips: the program's `lease.tpu` span of the lease
whose worker opened the run's chips (queued for free chips in the raylet's
books + the wait for a foreign holder, `[chips] waited` on stderr)."""

from perfbench.lib.setup_spans import stage_s


def read(run):
    return stage_s(run, "lease.tpu")
