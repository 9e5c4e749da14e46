"""Bytes one WHOLE decode step of the Keye model must move
(`lib.keye_counts.decode_step_bytes`: mixers, indexers, routers and the head
once, the weights of the experts the step TOUCHED once, the indexer keys it
scores and the K/V positions it chose; the counters are the program's own on
the `engine.step` spans of the traced seconds) / the chip's HBM bandwidth /
the step program's median device time in the trace (the SLOWEST bucket's)."""

from perfbench.lib import keye_counts
from perfbench.lib.peaks import peaks


def read(run):
    tr = run.get("trace")
    if not tr:
        return None
    step_ms = [v for k, v in tr["module_ms_p50"].items()
               if k.endswith("jit_decode_step")]
    args = [a for a in keye_counts.step_args(run, run["traffic"]["trace_window_s"])
            if "experts_touched" in a]
    if not step_ms or not args:
        return None
    mean = lambda key: sum(a[key] for a in args) / len(args)
    need = keye_counts.decode_step_bytes(
        run["config"], mean("index_rows"), mean("selected_rows"),
        mean("experts_touched"))
    bw = peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * (need / bw) / (max(step_ms) / 1e3)
