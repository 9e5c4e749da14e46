"""Matmul operations the forward and backward passes need for the traced
steps' tokens (from shapes: `lib.counts`; no recompute, no embedding gather)
/ (device busy seconds of the trace x chips x the chip's bf16 peak)."""

from perfbench.lib import counts
from perfbench.lib.peaks import peaks


def read(run):
    tr = run.get("trace")
    if not tr or "traced_steps" not in tr:
        return None
    flops = (counts.train_matmul_flops_per_token(run["config"], run["seq"])
             * tr["traced_steps"] * run["tokens_per_step"])
    peak = peaks(run["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * flops / (tr["busy_s"] * run["cell"]["chips"] * peak)
