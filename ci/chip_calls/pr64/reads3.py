"""PR 64, call 3: is the slow `deserialize_executable` off the main thread
glibc's per-thread arenas? The same reads as call 2 (the caller's in a row, a
fresh background thread's, one background thread's four in a row), in a
process as it is and in one that called `mallopt(M_ARENA_MAX, 1)` before
anything else (argument `one-arena`)."""

import ctypes
import sys

if "one-arena" in sys.argv:
    print("mallopt(M_ARENA_MAX, 1) ->", ctypes.CDLL("libc.so.6").mallopt(-8, 1), flush=True)

import json  # noqa: E402
import os  # noqa: E402
import threading  # noqa: E402
import time  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

from perfbench.lib import manifest  # noqa: E402

manifest.prepare_env(ROOT, False)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from perfbench.lib import hybrid_model  # noqa: E402
from ray_tpu.models import hybrid  # noqa: E402


def taken(fn, *a, **kw):
    t0 = time.perf_counter()
    out = fn(*a, **kw)
    return out, round(time.perf_counter() - t0, 3)


with open(os.path.join(ROOT, "perfbench/configs/kimi-linear-48b-a3b.1of4.json")) as f:
    conf = json.load(f)
cfg = hybrid_model.model_config(conf)
print(jax.devices(), flush=True)
A_PARAMS = jax.eval_shape(lambda k: hybrid.init_params(k, cfg), jax.random.PRNGKey(0))
I32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
P = [(1, 2048), (1, 4096), (2, 2048), (1, 1024)]
lower = lambda p: hybrid._prefill_first.lower(A_PARAMS, I32(*p), I32(p[0]), cfg)
for p in P:
    lower(p).compile()
for rnd in range(2):
    jax.clear_caches()
    lows = [lower(p) for p in P]
    print(json.dumps({"phase": "callers_in_a_row", "round": rnd,
                      "each_s": [taken(low.compile)[1] for low in lows]}), flush=True)
    for again in range(2):
        jax.clear_caches()
        low = lower(P[0])
        th = threading.Thread(target=low.compile, name="bg")
        print(json.dumps({"phase": "fresh_bg_thread_reads_one", "round": rnd,
                          "wall_s": taken(lambda: (th.start(), th.join()))[1]}), flush=True)
    jax.clear_caches()
    lows = [lower(p) for p in P]
    each = []
    th = threading.Thread(target=lambda: each.extend(taken(low.compile)[1] for low in lows))
    th.start()
    th.join()
    print(json.dumps({"phase": "one_bg_thread_reads_four", "round": rnd, "each_s": each}),
          flush=True)
