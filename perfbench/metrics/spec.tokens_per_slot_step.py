"""Tokens the requests received per busy slot and step over the window:
`tokens_out` / `active` summed over the `engine.step` spans that drafted
(first tokens of prompt passes drained in a span count with it; the one
junk step a finished request leaves in flight counts as busy). 1 without a
draft that holds, up to 2 with."""

from perfbench.lib.hybrid_counts import step_args


def read(run):
    args = [a for a in step_args(run, "tokens_out") if "draft_proposed" in a]
    busy = sum(a["active"] for a in args)
    if not busy:
        return None
    return sum(a["tokens_out"] for a in args) / busy
