"""`step_forms.py [busy ...]`: on the chip, the Granite cell's model at its
real size, alone (no server): device time of the decode step at several
numbers of busy slots and of a prompt pass at three buckets, and the
operations of each that take most time. One process, the chip's own."""
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)
import jax
import jax.numpy as jnp
import numpy as np

from perfbench.lib import granite_model, xplane
from ray_tpu.models import hybrid

conf = json.load(open(os.path.join(ROOT, "perfbench", "configs",
                                   "granite-4.0-h-small.1of2.json")))
cfg = granite_model.model_config(conf)
slots, max_len = conf["run"]["num_slots"], conf["run"]["max_len"]
params = granite_model.make_params(cfg, 1234)
cache = cfg.make_cache(slots, max_len)
out_dir = os.path.join(ROOT, "chiprun_out", "pr49", "trace_tmp")


def traced(fn, n, tag, top=22):
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir)
    t = time.time()
    for _ in range(n):
        fn()
    dt = (time.time() - t) / n
    jax.profiler.stop_trace()
    red = xplane.reduce(xplane.load(xplane.find_xplane(out_dir)), top=top)
    print(f"== {tag}: {1e3 * dt:.2f} ms a call by the host clock; module p50 "
          f"{ {k: round(v, 2) for k, v in red['module_ms_p50'].items()} }")
    for name, s in red["device_ops"]:
        print(f"   {1e3 * s / n:8.3f} ms  {name}")
    shutil.rmtree(out_dir, ignore_errors=True)


for busy in [int(a) for a in sys.argv[1:]] or [1, 10, 32]:
    lengths = np.zeros((slots,), np.int32)
    lengths[:busy] = 4000
    state = {"lengths": jnp.asarray(lengths), "tokens": jnp.ones((slots,), jnp.int32)}

    def step():
        state["lengths"], state["tokens"], rep = cache.decode(
            params, state["lengths"], state["tokens"], 8192, range(busy))
        np.asarray(rep)

    for _ in range(3):
        step()
    traced(step, 10, f"decode step, {busy} busy slots at ~4000 positions, attention bucket 8192")

for bucket in (4096, 8192, 12288):
    toks = jnp.ones((1, bucket), jnp.int32)
    lens = jnp.asarray([bucket - 7], jnp.int32)

    def pass_():
        first, rows = cache.prefill(params, toks, lens)
        np.asarray(first)

    pass_()
    traced(pass_, 2, f"prompt pass 1 x {bucket}", top=28)
print("peak bytes", jax.devices()[0].memory_stats().get("peak_bytes_in_use"))
