"""Programs the chip holder lowered before the window opened: its
`xla.compile` spans of a program's own lowering."""

from perfbench.lib.setup_spans import compiles


def read(run):
    c = compiles(run)
    return float(len(c["lower"])) if c else None
