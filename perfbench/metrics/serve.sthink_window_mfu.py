"""The whole served window's share of the chip's bf16 peak in the
SmallThinker cell: the model's FLOPs for the tokens the traced seconds
prefilled and decoded / (the traced seconds x the peak). Prompt passes that
ran whole there (`lib.cmda_counts.finished_passes`, their TRUE tokens): the
products of every token (attention's projections, router, its six experts,
the head for one row a prompt) and attention over the pairs the equations
name. Decode steps there: the products of each busy slot's token with the
assignments that LANDED (`expert_assignments`), the head, and attention over
the rows read and the token's own. All the program's own counters; nothing a
kernel visits beyond them is counted, nor is a pass that straddles an edge
of the trace."""

from perfbench.lib import sthink_counts
from perfbench.lib.peaks import peaks


def read(run):
    tr = run.get("trace")
    within = run["traffic"]["trace_window_s"]
    steps = sthink_counts.step_args(run, within) if tr else []
    if not steps:
        return None
    c = run["config"]
    need = sum(sthink_counts.product_flops(c, a["tokens"], head_rows=a["batch"])
               + sthink_counts.pass_attention_flops(c, a)
               for a in sthink_counts.finished_passes(run, within))
    pair = 4.0 * c["num_attention_heads"] * c["head_dim"]
    for a in steps:
        busy = a.get("active", 0)
        need += sthink_counts.product_flops(c, busy, a.get("expert_assignments", 0.0),
                                            head_rows=busy)
        need += pair * (sthink_counts.rows_per_step(c, a["window_rows"], a["full_rows"])
                        + busy * c["num_hidden_layers"])
    return 100.0 * need / peaks(run["device"]["kind"])["bf16_flops_per_s"] / tr["window_s"]
