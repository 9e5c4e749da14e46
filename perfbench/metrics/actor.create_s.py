"""The replica's constructor, as the worker that ran it timed it: the
program's `actor.create::<Class>` span in the process that holds the chip
(chip open, weights, engine, every program's trace and cache read or
compile, warm-up)."""

from perfbench.lib.setup_spans import stage_s


def read(run):
    return stage_s(run, "actor.create")
