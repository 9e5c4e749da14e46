"""Seconds the chip holder spent tracing and lowering its programs before
the window: the sum of each program's OWN trace and lower `xla.compile`
spans (a function traced inside a program makes no span of its own, so no
nested event is counted twice, as `compile.s` counts them)."""

from perfbench.lib.setup_spans import compiles


def read(run):
    c = compiles(run)
    return sum(e["dur"] for e in c["trace"] + c["lower"]) / 1e6 if c else None
