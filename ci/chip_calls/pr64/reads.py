"""PR 64, call 1: what the chip's client lets overlap. In ONE process, with
the Kimi cell's programs in the persistent cache: two `_prefill_first`
programs read one after the other and from two threads at once (wall against
the sum of jax's `cache_retrieval_time_sec`), one read beside another
program's trace + lower, a Python loop beside a read (does the read hold the
interpreter lock), and whether a program loaded from abstract arguments on a
background thread is the one a later plain call finds (no lowering on the
caller's thread, donated arguments included). Prints one JSON line a phase."""

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
print("cache dir:", os.environ["JAX_COMPILATION_CACHE_DIR"], "max",
      os.environ.get("JAX_COMPILATION_CACHE_MAX_SIZE"), flush=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import monitoring  # noqa: E402

from perfbench.lib import hybrid_model  # noqa: E402
from ray_tpu.models import hybrid  # noqa: E402

EVENTS = []


def _on_event(event, **kw):
    EVENTS.append((threading.current_thread().name, event, None, None))


def _on_duration(event, secs, **kw):
    name = str(kw.get("fun_name") or "")
    if event.endswith("jaxpr_trace_duration") and not name.startswith(
            ("_prefill_first", "decode_step", "_write_state")):
        return
    EVENTS.append((threading.current_thread().name, event.rsplit("/", 1)[-1],
                   round(secs, 4), name))


monitoring.register_event_listener(_on_event)
monitoring.register_event_duration_secs_listener(_on_duration)


def say(phase, **facts):
    print(json.dumps({"phase": phase, **facts}), flush=True)


def taken(fn, *a, **kw):
    t0 = time.perf_counter()
    out = fn(*a, **kw)
    return out, round(time.perf_counter() - t0, 4)


def retrievals():
    return [e[2] for e in EVENTS if e[1] == "cache_retrieval_time_sec"]


def hits_misses():
    return (sum(e[1].endswith("/cache_hits") for e in EVENTS),
            sum(e[1].endswith("/cache_misses") for e in EVENTS))


def sds(x):
    if isinstance(x, jax.Array):
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding if x._committed else None,
            weak_type=x.weak_type)
    x = np.asarray(x)
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


with open(os.path.join(ROOT, "perfbench/configs/kimi-linear-48b-a3b.1of4.json")) as f:
    conf = json.load(f)
run = conf["run"]
cfg = hybrid_model.model_config(conf)
SLOTS, MAX_LEN = run["num_slots"], run["max_len"]
print(jax.devices(), flush=True)
params, t = taken(lambda: jax.block_until_ready(hybrid_model.make_params(cfg, 6400000001)))
say("params", seconds=t, hits_misses=hits_misses(), retrieval=retrievals())
cache = cfg.make_cache(SLOTS, MAX_LEN)
lengths = jnp.zeros((SLOTS,), jnp.int32)
tokens = jnp.zeros((SLOTS,), jnp.int32)
A_PARAMS, A_STATE = jax.tree.map(sds, params), jax.tree.map(sds, cache.state)
I32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)


def lower_prefill(nb, bucket):
    return hybrid._prefill_first.lower(A_PARAMS, I32(nb, bucket), I32(nb), cfg)


def lower_decode(attn_len):
    return hybrid.decode_step.lower(
        A_PARAMS, A_STATE, I32(SLOTS), I32(SLOTS),
        jax.ShapeDtypeStruct((SLOTS,), jnp.bool_), cfg, attn_len)


def lower_write(prefill_lowered, nb):
    first, rows = prefill_lowered.out_info
    plain = lambda o: jax.ShapeDtypeStruct(o.shape, o.dtype)
    return hybrid._write_state.lower(
        A_STATE, I32(SLOTS), I32(SLOTS), I32(nb), jax.tree.map(plain, rows),
        I32(nb), plain(first))


P = [(1, 2048), (1, 4096), (2, 2048), (1, 1024)]

# ---- phase 0: every program of this call into the cache (cold if it must),
# from abstract arguments on a background thread; then the plain calls
EVENTS.clear()


def ahead():
    for nb, bucket in P[:2]:
        low = lower_prefill(nb, bucket)
        low.compile()
        lower_write(low, nb).compile()
    lower_decode(MAX_LEN).compile()


th = threading.Thread(target=ahead, name="ahead")
_, t = taken(lambda: (th.start(), th.join()))
say("ahead_load", seconds=t, hits_misses=hits_misses(), retrieval=retrievals(),
    events=[e for e in EVENTS if e[2] is not None and e[3].startswith("jit(")])
EVENTS.clear()
t0 = time.perf_counter()
for nb, bucket in P[:2]:
    lens = jnp.asarray([1] * nb, jnp.int32)
    first, rows = cache.prefill(params, jnp.asarray([[0] * bucket] * nb, jnp.int32), lens)
    lengths, tokens = cache.write(lengths, tokens, jnp.asarray([SLOTS] * nb, jnp.int32),
                                  rows, lens, first)
lengths, tokens, _ = cache.decode(params, lengths, tokens, MAX_LEN, ())
jax.block_until_ready(cache.state)
own = [e for e in EVENTS if e[3] and any(
    n in e[3] for n in ("_prefill_first", "decode_step", "_write_state"))]
say("plain_calls_after_ahead", seconds=round(time.perf_counter() - t0, 4),
    lowerings_of_the_three=[e for e in own if e[1] != "jaxpr_trace_duration"],
    trace_events=[e for e in own if e[1] == "jaxpr_trace_duration"],
    hits_misses=hits_misses())
del first, rows
for nb, bucket in P[2:]:   # the other two, for the rounds below
    lower_prefill(nb, bucket).compile()


def fresh():
    jax.clear_caches()
    EVENTS.clear()


# ---- A: one after the other
for rnd in range(2):
    fresh()
    lows, t_low = zip(*(taken(lower_prefill, *p) for p in P[:2]))
    reads = [taken(low.compile)[1] for low in lows]
    say("A_sequential", round=rnd, trace_lower_s=t_low, read_wall_s=reads,
        retrieval=retrievals(), hits_misses=hits_misses())

    # ---- B: two reads at once
    fresh()
    lows = [lower_prefill(*p) for p in P[:2]]
    walls = [0.0, 0.0]

    def read(i):
        walls[i] = taken(lows[i].compile)[1]

    ths = [threading.Thread(target=read, args=(i,), name=f"read{i}") for i in (0, 1)]
    _, t = taken(lambda: ([x.start() for x in ths], [x.join() for x in ths]))
    say("B_two_reads_at_once", round=rnd, wall_s=t, each_s=walls,
        retrieval=retrievals(), hits_misses=hits_misses())

    # ---- C: one read beside the next program's trace + lower
    fresh()
    low0 = lower_prefill(*P[0])
    wall = [0.0]
    th = threading.Thread(target=lambda: wall.__setitem__(0, taken(low0.compile)[1]),
                          name="read0")
    t0 = time.perf_counter()
    th.start()
    low1, t_low = taken(lower_prefill, *P[1])
    th.join()
    both = round(time.perf_counter() - t0, 4)
    say("C_read_beside_trace_lower", round=rnd, wall_s=both, read_s=wall[0],
        trace_lower_s=t_low, retrieval=retrievals())

    # ---- D: a Python loop beside a read
    def spin(seconds):
        n, end = 0, time.perf_counter() + seconds
        while time.perf_counter() < end:
            n += 1
        return n / seconds

    alone = spin(1.0)
    fresh()
    low0 = lower_prefill(*P[0])
    th = threading.Thread(target=low0.compile, name="read0")
    th.start()
    beside = spin(1.0)
    th.join()
    say("D_python_loop_beside_read", round=rnd, loops_per_s_alone=round(alone),
        loops_per_s_beside=round(beside), ratio=round(beside / alone, 3),
        retrieval=retrievals())

    # ---- E: four reads at once, and the same four in a row
    fresh()
    lows = [lower_prefill(*p) for p in P]
    ths = [threading.Thread(target=low.compile, name=f"read{i}")
           for i, low in enumerate(lows)]
    _, t = taken(lambda: ([x.start() for x in ths], [x.join() for x in ths]))
    got = retrievals()
    fresh()
    lows = [lower_prefill(*p) for p in P]
    _, t_row = taken(lambda: [low.compile() for low in lows])
    say("E_four_reads", round=rnd, at_once_wall_s=t, at_once_retrieval=got,
        in_a_row_wall_s=t_row, in_a_row_retrieval=retrievals())
