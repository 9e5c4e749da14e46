"""Bytes one decode step of the hybrid model must move (`lib.hybrid_counts`:
the fixed weights once, the weights of the experts the step touched once,
KDA state read and written, live latent rows read; the counters are the
program's own on the `engine.step` spans of the traced seconds) / the
chip's HBM bandwidth / the step program's median device time in the trace."""

from perfbench.lib import hybrid_counts
from perfbench.lib.peaks import peaks


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    step_ms = [v for k, v in tr["module_ms_p50"].items()
               if k.endswith("jit_decode_step")]
    args = hybrid_counts.step_args(run, "experts_touched",
                                   run["traffic"]["trace_window_s"])
    if not step_ms or not args:
        return None
    mean = lambda key: sum(a[key] for a in args) / len(args)
    need = hybrid_counts.decode_step_bytes(
        run["config"], mean("state_slots"), mean("latent_rows"),
        mean("experts_touched"))
    bw = peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / (max(step_ms) / 1e3)
