"""Seconds the chip holder spent in the backend's compiler before the
window: the sum of the backend-compile `xla.compile` spans that the
persistent cache did not answer (`cache` `miss` or `off`)."""

from perfbench.lib.setup_spans import compiles


def read(run):
    c = compiles(run)
    if c is None:
        return None
    return sum(e["dur"] for e in c["backend"]
               if e["args"].get("cache") in ("miss", "off")) / 1e6
