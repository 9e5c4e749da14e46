"""What of the chip holder's spawn was the interpreter and the imports up to
its connecting to the raylet: `imports_us` of the program's `worker.boot`
span (from the stamp the raylet put in the spawn's environment)."""

from perfbench.lib.setup_spans import stage_s


def read(run):
    return stage_s(run, "worker.boot", "imports_us")
