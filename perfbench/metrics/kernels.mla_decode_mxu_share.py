"""The `mla_decode_attention` Pallas kernel's share of the MXU's bf16 peak
over the traced seconds: events x the operations one call has to do at the
mean live rows (`lib.pangu_counts.mla_decode_flops` a layer: 2 positions x
128 heads against every live row, scores over the row's 576 values and
values over the rank; the rows are the program's own `latent_rows` on the
`engine.step` spans of those seconds) / the peak / the events' summed device
time. The kernel computes whole blocks of 512 rows x 640 stored lanes, so
what it does past a slot's last row and over the 64 zero lanes of each row
is not counted as useful."""

from perfbench.lib import pangu_counts
from perfbench.lib.hybrid_counts import step_args
from perfbench.lib.peaks import peaks


def read(run):
    events, seconds = ((run.get("trace") or {}).get("kernel_calls") or {}).get(
        "mla_decode_attention") or (0, 0.0)
    args = [a for a in step_args(run, "latent_rows", run["traffic"]["trace_window_s"])
            if "draft_proposed" in a] if events else []
    if not seconds or not args:
        return None
    c = run["config"]
    rows = sum(a["latent_rows"] for a in args) / len(args)
    per_call = pangu_counts.mla_decode_flops(
        c, rows, 1 + c["num_nextn_predict_layers"]) / pangu_counts.latent_layers(c)
    return 100.0 * events * per_call / peaks(run["device"]["kind"])[
        "bf16_flops_per_s"] / seconds
