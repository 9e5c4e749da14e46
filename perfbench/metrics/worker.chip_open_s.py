"""jax's backend opening the run's chips (libtpu), apart from `import jax`:
the program's `chip.open` span around the call that initialised the
backends, whoever made it."""

from perfbench.lib.setup_spans import stage_s


def read(run):
    return stage_s(run, "chip.open")
