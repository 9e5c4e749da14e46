"""Chunk summaries written a decode step, mean over the window's steps (the
program's `chunks_closed` on `engine.step`, which rides the step's report):
a busy slot closes a chunk every 16th step, each at a step of its own, so
this is near the busy slots / 16."""

from perfbench.lib.hybrid_counts import step_args


def read(run):
    got = [a["chunks_closed"] for a in step_args(run, "chunks_closed")]
    return sum(got) / len(got) if got else None
