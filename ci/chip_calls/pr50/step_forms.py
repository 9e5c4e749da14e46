"""`step_forms.py <checkout> kimi|pangu`: ON THE CHIP, the cell's model at its
real size, alone (no server), one process: the decode step at the cell's
usual load and three prompt passes, each by the host clock (calls ending in
a read of their result) and by the device's (the module's median in a
trace). Same seed, same weights, same tokens on both trees: is the COMPILED
work the parent's?"""
import json
import os
import shutil
import sys
import time

root = os.path.abspath(sys.argv[1])
which = sys.argv[2]
sys.path.insert(0, root)
os.chdir(root)
from perfbench.lib.manifest import prepare_env

prepare_env(root, False)
import jax
import jax.numpy as jnp
import numpy as np

from perfbench.lib import hybrid_model, pangu_model, xplane

mod, file, busy, at, attn_len, passes = {
    "kimi": (hybrid_model, "kimi-linear-48b-a3b.1of4.json", 12, 1500, 2048,
             [(1, 256), (4, 1024), (1, 4096)]),
    "pangu": (pangu_model, "openpangu-ultra-moe-718b.1of32.json", 6, 3000, 4096,
              [(1, 1024), (2, 2048), (1, 8191)]),
}[which]
conf = json.load(open(os.path.join(root, "perfbench", "configs", file)))
cfg = mod.model_config(conf)
slots, max_len = conf["run"]["num_slots"], conf["run"]["max_len"]
params = mod.make_params(cfg, 1234)
cache = cfg.make_cache(slots, max_len)
out_dir = os.path.join("/root/repo", "chiprun_out", "pr50", f"trace_tmp_{os.getpid()}")


def timed(fn, n, tag):
    for _ in range(3):
        fn()
    t = time.perf_counter()
    for _ in range(n):
        fn()
    host = (time.perf_counter() - t) / n
    shutil.rmtree(out_dir, ignore_errors=True)
    jax.profiler.start_trace(out_dir)
    for _ in range(min(n, 10)):
        fn()
    jax.profiler.stop_trace()
    red = xplane.reduce(xplane.load(xplane.find_xplane(out_dir)), top=3)
    shutil.rmtree(out_dir, ignore_errors=True)
    print(json.dumps({"what": tag, "host_ms": round(1e3 * host, 3),
                      "module_ms_p50": {k: round(v, 3) for k, v in
                                        red["module_ms_p50"].items()}}), flush=True)


lengths = np.zeros((slots,), np.int32)
lengths[:busy] = at
state = {"lengths": jnp.asarray(lengths),
         "tokens": jnp.asarray(np.arange(slots) % 977 + 1, jnp.int32)}


def step():
    state["lengths"], state["tokens"], rep = cache.decode(
        params, state["lengths"], state["tokens"], attn_len, range(busy))
    np.asarray(rep)


timed(step, 100, f"{which} decode step, {busy} of {slots} slots busy at ~{at}, "
                 f"attention bucket {attn_len}")
rng = np.random.default_rng(7)
for nb, bucket in passes:
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (nb, bucket)), jnp.int32)
    lens = jnp.asarray([bucket - 7] * nb, jnp.int32)

    def prompt_pass():
        first, rows = cache.prefill(params, toks, lens)
        np.asarray(first)

    timed(prompt_pass, 5, f"{which} prompt pass {nb} x {bucket}")
print(json.dumps({"peak_bytes": jax.devices()[0].memory_stats().get("peak_bytes_in_use")}))
os._exit(0)
