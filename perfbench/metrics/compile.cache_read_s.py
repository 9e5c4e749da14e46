"""Seconds the chip holder spent READING executables from jax's persistent
cache before the window: the sum of `retrieval_us` over the backend-compile
`xla.compile` spans whose `cache` is `hit`."""

from perfbench.lib.setup_spans import compiles


def read(run):
    c = compiles(run)
    if c is None:
        return None
    return sum(e["args"].get("retrieval_us", 0.0) for e in c["backend"]
               if e["args"].get("cache") == "hit") / 1e6
