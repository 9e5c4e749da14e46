"""Seconds jax spent tracing, lowering and compiling (or reading its
persistent cache) before the window opened, from jax's own duration events
in the process that holds the chip."""


def read(run):
    return run["compile_setup"]["compile_s"]
