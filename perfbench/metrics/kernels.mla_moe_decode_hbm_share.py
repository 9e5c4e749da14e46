"""Bytes one decode step of the openPangu cut must move (`lib.pangu_counts`:
the fixed weights once, the weights of the experts the step touched once,
the live latent rows read; the counters are the program's own on the
`engine.step` spans of the traced seconds) / the chip's HBM bandwidth / the
step program's median device time in the trace (the slowest bucket's). The
step is not all bandwidth: its attention at 128 heads x 2 positions is
bound by the MXU (`kernels.mla_decode_mxu_share`)."""

from perfbench.lib import pangu_counts
from perfbench.lib.hybrid_counts import step_args
from perfbench.lib.peaks import peaks


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    step_ms = [v for k, v in tr["module_ms_p50"].items()
               if k.endswith("jit_decode_step")]
    args = [a for a in step_args(run, "latent_rows", run["traffic"]["trace_window_s"])
            if "draft_proposed" in a]
    if not step_ms or not args:
        return None
    mean = lambda key: sum(a[key] for a in args) / len(args)
    need = pangu_counts.decode_step_bytes(
        run["config"], mean("latent_rows"), mean("experts_touched"))
    bw = peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / (max(step_ms) / 1e3)
