"""PR 64, call 2: why a read from a thread that is not the caller's took 5-8 s
in call 1 where the caller's own takes 1 s. The read's three stages (the
cache file under jax's lock, the decompression, the client's
`deserialize_executable`) timed by thread, with the main thread blocked in a
join, asleep in a loop, tracing, or reading itself; and the other division of
the work: the CALLER reads while one background thread traces and lowers."""

import json
import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

from perfbench.lib import manifest  # noqa: E402

manifest.prepare_env(ROOT, False)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax._src import compilation_cache as cc  # noqa: E402

from perfbench.lib import hybrid_model  # noqa: E402
from ray_tpu.models import hybrid  # noqa: E402

STAGES = []


def timed_get(cache_key, compile_options, backend, executable_devices):
    cache = cc._get_cache(backend)
    if cache is None:
        return None, None
    t0 = time.perf_counter()
    blob = cache.get(cache_key)
    if blob is None:
        STAGES.append((threading.current_thread().name, "miss"))
        return None, None
    t1 = time.perf_counter()
    blob = cc.decompress_executable(blob)
    serialized, compile_time = cc.extract_executable_and_time(blob)
    t2 = time.perf_counter()
    loaded = backend.deserialize_executable(serialized, executable_devices,
                                            compile_options)
    t3 = time.perf_counter()
    STAGES.append((threading.current_thread().name, round(t1 - t0, 3),
                   round(t2 - t1, 3), round(t3 - t2, 3), len(serialized) >> 20))
    return loaded, compile_time


cc.get_executable_and_time = timed_get


def say(phase, **facts):
    print(json.dumps({"phase": phase, **facts,
                      "stages_thread_file_unzip_deserialize_mib": STAGES[:]}), flush=True)
    STAGES.clear()


def taken(fn, *a, **kw):
    t0 = time.perf_counter()
    out = fn(*a, **kw)
    return out, round(time.perf_counter() - t0, 3)


with open(os.path.join(ROOT, "perfbench/configs/kimi-linear-48b-a3b.1of4.json")) as f:
    conf = json.load(f)
cfg = hybrid_model.model_config(conf)
SLOTS, MAX_LEN = conf["run"]["num_slots"], conf["run"]["max_len"]
print(jax.devices(), flush=True)
A_PARAMS = jax.eval_shape(lambda k: hybrid.init_params(k, cfg), jax.random.PRNGKey(0))
I32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
P = [(1, 2048), (1, 4096), (2, 2048), (1, 1024)]
Q = [(1, 512), (1, 256), (2, 512), (2, 256)]      # traced beside reads, never read


def lower(p):
    return hybrid._prefill_first.lower(A_PARAMS, I32(*p), I32(p[0]), cfg)


def fresh():
    jax.clear_caches()
    STAGES.clear()


for p in P:   # all four in the cache
    lower(p).compile()
say("into_the_cache")

for rnd in range(2):
    fresh()
    lows = [lower(p) for p in P]
    say("R1_callers_reads_in_a_row", round=rnd,
        each_s=[taken(low.compile)[1] for low in lows])

    fresh()
    low = lower(P[0])
    th = threading.Thread(target=low.compile, name="bg")
    _, t = taken(lambda: (th.start(), th.join()))
    say("R2_bg_read_main_in_join", round=rnd, wall_s=t)

    fresh()
    low = lower(P[0])
    th = threading.Thread(target=low.compile, name="bg")

    def nap():
        th.start()
        while th.is_alive():
            time.sleep(0.001)

    say("R3_bg_read_main_naps_1ms", round=rnd, wall_s=taken(nap)[1])

    # the other division: the caller reads, one background thread traces + lowers
    fresh()
    lows = [lower(p) for p in P]
    traced = []
    th = threading.Thread(
        target=lambda: traced.extend(taken(lower, q)[1] for q in Q), name="tracer")
    t0 = time.perf_counter()
    th.start()
    reads = [taken(low.compile)[1] for low in lows]
    t_reads = round(time.perf_counter() - t0, 3)
    th.join()
    say("R4_caller_reads_beside_bg_tracer", round=rnd, reads_s=reads,
        reads_wall_s=t_reads, bg_trace_lower_s=traced,
        both_wall_s=round(time.perf_counter() - t0, 3))
    fresh()
    say("R4b_trace_lower_alone_main", round=rnd,
        each_s=[taken(lower, q)[1] for q in Q])

    fresh()
    lows = [lower(p) for p in P[:2]]
    th = threading.Thread(target=lows[1].compile, name="bg")
    t0 = time.perf_counter()
    th.start()
    _, t_main = taken(lows[0].compile)
    th.join()
    say("R5_caller_and_bg_read_at_once", round=rnd, main_read_s=t_main,
        wall_s=round(time.perf_counter() - t0, 3))

    fresh()
    low = lower(P[0])
    sys.setswitchinterval(1e-4)
    th = threading.Thread(target=low.compile, name="bg")
    _, t = taken(lambda: (th.start(), th.join()))
    sys.setswitchinterval(5e-3)
    say("R6_bg_read_switch_interval_100us", round=rnd, wall_s=t)

    # a bg thread that has read before: is it the thread's first read that is slow
    fresh()
    lows = [lower(p) for p in P]
    each = []
    th = threading.Thread(target=lambda: each.extend(taken(low.compile)[1] for low in lows),
                          name="bg")
    _, t = taken(lambda: (th.start(), th.join()))
    say("R7_one_bg_thread_reads_four_in_a_row", round=rnd, wall_s=t, each_s=each)
