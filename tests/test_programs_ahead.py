"""An engine's programs loaded ahead of their first call (`models/programs.py`):
what a life lists, what the next life loads and on which threads, that the
ahead lowering is the call's own down to the persistent cache's key, and
everything that must leave a replica on the plain path.

Lives share one process: `jax.clear_caches()` between two of them leaves
the second what a fresh process has, the persistent cache. A life `as_actor`
runs as a replica's does: the engine's caller is a thread of its own and the
process's main thread is lent (`util/main_thread.py`), as a worker's is."""

import json
import os
import threading
import time

import jax
import pytest
from jax import monitoring

from ray_tpu.models import ModelConfig, hybrid, init_params, programs
from ray_tpu.models.serving import ContinuousBatchingEngine, DenseKVCache
from ray_tpu.util import main_thread, tracing

H = hybrid.HybridConfig
KINDS = {"DenseKVCache": ModelConfig.tiny, "HybridCache": H.tiny_hybrid,
         "RunsCache": H.tiny_runs, "EvaCache": H.tiny_eva,
         "DsaCache": H.tiny_dsa, "SwaCache": H.tiny_swa}
OWN = ("prefill_slots", "_write_slots", "decode_step_fused",
       "_prefill_first", "_write_state", "decode_step")


class Events:
    """jax's compile events of the engine's six programs, by thread."""

    def __init__(self):
        self.rows, self.on = [], True
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if self.on and event.endswith(("/cache_hits", "/cache_misses")):
            self.rows.append((threading.current_thread().name,
                              event.rsplit("/", 1)[-1], ""))

    def _duration(self, event, secs, **kw):
        name = str(kw.get("fun_name") or "")
        bare = name[4:-1] if name.startswith("jit(") else name
        if self.on and bare in OWN and event.startswith("/jax/core/compile/"):
            self.rows.append((threading.current_thread().name,
                              event.rsplit("/", 1)[-1], bare))

    def of(self, what, on=None):
        """Rows of event `what`, on threads whose name starts with `on`."""
        return [r for r in self.rows if r[1] == what and r[0].startswith(on or "")]


@pytest.fixture(scope="module")
def events():
    ev = Events()
    yield ev
    ev.on = False   # jax keeps no way to unregister one listener


@pytest.fixture
def cache_dir(tmp_path, events):
    """A persistent cache that keeps every program, for this test alone."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    for n, v in zip(names, (str(tmp_path / "cache"), 0, -1)):
        jax.config.update(n, v)
    cc.reset_cache()
    jax.clear_caches()
    tracing.clear()
    events.rows.clear()
    yield str(tmp_path / "cache")
    for n, v in before.items():
        jax.config.update(n, v)
    cc.reset_cache()
    jax.clear_caches()


def make(kind):
    cfg = KINDS[kind]()
    make_params = init_params if kind == "DenseKVCache" else hybrid.init_params
    return cfg, make_params(jax.random.PRNGKey(0), cfg)


def life(cfg, params, events=None, *, num_slots=3, max_len=64, prompts=((1, 2, 3),),
         new_tokens=3, as_actor=False):
    """One engine life: constructor, requests, teardown. -> (the engine,
    what it answered)."""
    jax.clear_caches()
    if events is not None:
        events.rows.clear()
    tracing.clear()
    got = []

    def live():
        while as_actor and main_thread._jobs is None:   # as a worker's: lent first
            time.sleep(0.001)
        eng = ContinuousBatchingEngine(params, cfg, num_slots=num_slots, max_len=max_len)
        out = [eng.generate(list(p), max_new_tokens=new_tokens) for p in prompts]
        eng.stop_driver()
        got.extend((eng, out))

    if not as_actor:
        live()
    else:
        caller = threading.Thread(target=live, name="caller")
        caller.start()
        main_thread.serve(lambda: not caller.is_alive(), poll_s=0.01)
        caller.join()
    return tuple(got)


def list_files(cache_dir):
    return sorted(os.path.join(cache_dir, f) for f in os.listdir(cache_dir)
                  if f.startswith("programs-") and f.endswith("-cache")) \
        if os.path.isdir(cache_dir) else []


def listed(cache_dir):
    return [[json.loads(l) for l in open(f).read().splitlines()]
            for f in list_files(cache_dir)]


def ahead_span():
    return [e["args"] for e in tracing.get_events() if e["name"] == "programs.ahead"]


@pytest.mark.parametrize("kind", list(KINDS))
def test_a_replicas_second_life_loads_the_list_off_the_callers_thread(
        kind, cache_dir, events):
    cfg, params = make(kind)
    eng, first = life(cfg, params, events, as_actor=True)
    assert type(eng.cache).__name__ == kind
    (rows,) = listed(cache_dir)
    assert rows[0]["cache"] == kind and rows[0]["num_slots"] == 3
    keys = [tuple(r) for r in rows[1:]]
    assert keys[0][0] == "admit" and ("decode", keys[-1][1]) == keys[-1], keys
    assert not ahead_span()     # a first life has no list to replay
    assert not events.of("jaxpr_to_mlir_module_duration", "programs-")
    assert not events.of("backend_compile_duration", "MainThread")
    plain = sorted(r[2] for r in events.of("jaxpr_to_mlir_module_duration", "caller"))
    assert len(plain) == len(keys) + sum(k[0] == "admit" for k in keys)

    eng, second = life(cfg, params, events, as_actor=True)
    assert second == first
    # every program the first life lowered and read on its caller's thread,
    # the second lowered on one thread and read on the lent one, from the
    # cache, under the first life's keys: nothing is left to the caller
    assert sorted(r[2] for r in events.of(
        "jaxpr_to_mlir_module_duration", "programs-ahead")) == plain
    assert sorted(r[2] for r in events.of("backend_compile_duration", "MainThread")) == plain
    assert not events.of("jaxpr_to_mlir_module_duration", "caller")
    assert not events.of("backend_compile_duration", "caller")
    assert not events.of("cache_misses")
    assert len(events.of("cache_hits", "MainThread")) == len(plain)
    (said,) = ahead_span()
    assert said["listed"] == said["loaded"] == len(plain) and said["failed"] == 0
    assert said["ready_at_first_call"] <= len(plain) and said["wall_us"] > 0
    assert listed(cache_dir) == [rows]      # the same list, written anew
    assert not [t for t in threading.enumerate() if t.name.startswith("programs-")]


def test_where_no_main_thread_is_lent_the_caller_reads(cache_dir, events):
    """A script's engine (its caller is the main thread, which serves nobody):
    the second life traces and lowers off the caller's thread, and the
    caller's own first call reads: the read is fastest where it is."""
    cfg, params = make("HybridCache")
    _, first = life(cfg, params, events)
    mine = threading.current_thread().name
    plain = sorted(r[2] for r in events.of("jaxpr_to_mlir_module_duration", mine))
    assert plain == ["_prefill_first", "_write_state", "decode_step"]
    _, second = life(cfg, params, events)
    assert second == first
    assert sorted(r[2] for r in events.of(
        "jaxpr_to_mlir_module_duration", "programs-ahead")) == plain
    assert not events.of("jaxpr_to_mlir_module_duration", mine)
    assert sorted(r[2] for r in events.of("backend_compile_duration", mine)) == plain
    assert not events.of("backend_compile_duration", "programs-")
    assert not events.of("cache_misses") and len(events.of("cache_hits", mine)) >= 3
    (said,) = ahead_span()
    assert said["listed"] == said["loaded"] == 3 and said["failed"] == 0


def test_donated_arguments_hash_to_the_calls_key(cache_dir, events):
    """The second life reads what the FIRST life's plain calls wrote: the
    ahead lowering of the step that donates its state is the call's own."""
    cfg, params = make("HybridCache")
    life(cfg, params, events, as_actor=True)
    wrote = {r[2] for r in events.of("backend_compile_duration", "caller")}
    assert {"_prefill_first", "_write_state", "decode_step"} <= wrote
    life(cfg, params, events, as_actor=True)
    assert not events.of("cache_misses")
    assert {r[2] for r in events.of("backend_compile_duration", "MainThread")} == wrote


@pytest.mark.parametrize("other", ["cfg", "num_slots", "max_len", "version"])
def test_a_list_of_another_engine_is_not_replayed(other, cache_dir, events, monkeypatch):
    cfg, params = make("DenseKVCache")
    life(cfg, params)
    kw = {}
    if other == "cfg":
        import dataclasses
        cfg = dataclasses.replace(cfg, rope_theta=cfg.rope_theta * 2)
    elif other == "version":
        monkeypatch.setattr(jax, "__version__", "0.0.0")
    else:
        kw[other] = 4 if other == "num_slots" else 128
    life(cfg, params, events, **kw)
    assert not ahead_span()
    assert not events.of("jaxpr_to_mlir_module_duration", "programs-")
    assert len(listed(cache_dir)) == 2     # a list each


def test_a_header_that_is_not_this_engines_is_not_replayed(cache_dir, events):
    cfg, params = make("DenseKVCache")
    eng, _ = life(cfg, params)
    (path,) = list_files(cache_dir)
    lines = open(path).read().splitlines()
    open(path, "w").write("\n".join(
        [json.dumps({**json.loads(lines[0]), "max_len": 65})] + lines[1:]) + "\n")
    life(cfg, params, events)
    assert not ahead_span()


def test_an_entry_that_no_longer_lowers_is_skipped_and_dropped(cache_dir, events, capfd):
    cfg, params = make("DenseKVCache")
    _, first = life(cfg, params)
    (path,) = list_files(cache_dir)
    with open(path, "a") as f:    # a step whose window is no whole number
        f.write(json.dumps(["decode", -7]) + "\n")
    _, second = life(cfg, params, events)
    assert second == first
    (said,) = ahead_span()
    assert said["failed"] == 1 and said["loaded"] == said["listed"] - 1 == 3
    assert capfd.readouterr().err.count("[programs] skipped") == 1
    assert ["decode", -7] not in listed(cache_dir)[0]


def test_no_cache_directory_no_file_and_no_thread(tmp_path, events):
    assert not jax.config.jax_compilation_cache_dir
    cfg, params = make("DenseKVCache")
    eng, _ = life(cfg, params, events)
    assert eng.cache.programs is programs.Direct
    assert not [t for t in threading.enumerate() if t.name.startswith("programs-")]
    assert not ahead_span()


def test_a_cache_that_is_no_local_directory_keeps_no_list():
    before = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", "gs://bucket/cache")
    try:
        assert programs.list_path(object(), None, 1, 8) == (None, {})
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_a_seen_key_never_touches_the_list_again(cache_dir, monkeypatch):
    cfg, params = make("DenseKVCache")
    eng = ContinuousBatchingEngine(params, cfg, num_slots=3, max_len=256)
    calls = []
    record = eng.cache.programs._record
    monkeypatch.setattr(eng.cache.programs, "_record",
                        lambda key: (calls.append(key), record(key)))
    eng.generate([1, 2, 3], max_new_tokens=100)
    steps = [e for e in tracing.get_events() if e["name"] == "engine.step"]
    assert len(steps) >= 100
    # one admission, and a step a window the answer grew into
    assert calls == [("admit", 1, 8), ("decode", 64), ("decode", 128)]
    first_calls = []
    slow = eng.cache.programs._loads.get
    monkeypatch.setattr(eng.cache.programs, "_loads", type(
        "Counted", (dict,), {"get": lambda self, k: (first_calls.append(k), slow(k))[1]})())
    eng.generate([4, 5, 6], max_new_tokens=100)
    assert calls == calls[:3] and not first_calls
    eng.stop_driver()


def test_a_call_that_arrives_mid_load_waits_and_does_not_trace_twice(
        cache_dir, events, monkeypatch):
    cfg, params = make("HybridCache")
    _, first = life(cfg, params)
    lowered = hybrid.HybridCache.lowered

    def slow(self, key, *avals):
        time.sleep(0.5)     # the caller is at the gate well before this ends
        return lowered(self, key, *avals)

    monkeypatch.setattr(hybrid.HybridCache, "lowered", slow)
    _, second = life(cfg, params, events)
    assert second == first
    (said,) = ahead_span()
    assert said["waited_us"] > 400_000 and said["ready_at_first_call"] == 0
    assert len(events.of("jaxpr_to_mlir_module_duration")) == said["loaded"] == 3
    assert len(events.of("jaxpr_to_mlir_module_duration", "programs-ahead")) == 3


def test_a_caller_that_waits_is_served_before_the_lists_order(cache_dir, monkeypatch):
    cfg, params = make("DenseKVCache")
    life(cfg, params, prompts=((1, 2, 3), tuple(range(1, 20))))   # buckets 8, 32
    order, lowered, gate = [], DenseKVCache.lowered, threading.Event()

    def held(self, key, *avals):
        order.append(key)
        gate.wait(10)
        return lowered(self, key, *avals)

    monkeypatch.setattr(DenseKVCache, "lowered", held)
    jax.clear_caches()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=3, max_len=64)
    keys = list(eng.cache.programs._loads)
    assert keys[0] == ("admit", 1, 8) and ("admit", 1, 32) in keys[2:]
    threading.Timer(0.3, gate.set).start()
    eng.generate(list(range(1, 20)), max_new_tokens=2)    # asks for the later one
    eng.stop_driver()
    assert order[:2] == [("admit", 1, 8), ("admit", 1, 32)], order


def test_teardown_joins_the_threads(cache_dir, monkeypatch):
    cfg, params = make("DenseKVCache")
    life(cfg, params)
    lowered = DenseKVCache.lowered
    monkeypatch.setattr(DenseKVCache, "lowered", lambda self, key, *avals: (
        time.sleep(0.3), lowered(self, key, *avals))[1])
    jax.clear_caches()
    tracing.clear()
    eng = ContinuousBatchingEngine(params, cfg, num_slots=3, max_len=64)
    assert [t for t in threading.enumerate() if t.name == "programs-ahead"]
    eng.stop_driver()
    assert not [t for t in threading.enumerate() if t.name.startswith("programs-")]
    (said,) = ahead_span()     # said at teardown, with what had loaded
    assert said["loaded"] < 3
    assert eng.generate([1, 2, 3], max_new_tokens=2)   # and the engine serves


def test_a_truncated_list_is_read_as_far_as_it_is_whole(tmp_path):
    header = {"cache": "X", "max_len": 8}
    path = str(tmp_path / "list.jsonl")
    whole = [json.dumps(header), '["admit", 1, 8]', '["decode", 64]', '["admit", 2, 16]']
    open(path, "w").write("\n".join(whole) + "\n")
    assert programs.read_list(path, header) == [("admit", 1, 8), ("decode", 64),
                                                ("admit", 2, 16)]
    open(path, "w").write("\n".join(whole)[:-4])       # cut inside the last line
    assert programs.read_list(path, header) == [("admit", 1, 8), ("decode", 64)]
    open(path, "w").write("\n".join(whole[:2]) + '\n{"no": "key"}\nnoise\n["decode", 64]\n'
                          '["decode", 64]\n["decode", "x"]\n')
    assert programs.read_list(path, header) == [("admit", 1, 8), ("decode", 64)]
    assert programs.read_list(path, {**header, "max_len": 9}) == []
    open(path, "w").write(json.dumps(header)[:-3])     # cut inside the header
    assert programs.read_list(path, header) == []
    assert programs.read_list(str(tmp_path / "none"), header) == []


def test_the_list_is_an_entry_jaxs_eviction_can_account_for(cache_dir):
    """Named as the directory's owner names its own, with the stamp the
    eviction reads: a capped cache that walks its entries walks the list too,
    and a list it evicts costs a first life, no more."""
    from jax._src import lru_cache
    from jax.experimental.compilation_cache import compilation_cache as cc

    jax.config.update("jax_compilation_cache_max_size", 1 << 30)   # jax stamps its own
    cc.reset_cache()
    try:
        cfg, params = make("DenseKVCache")
        life(cfg, params)
    finally:
        jax.config.update("jax_compilation_cache_max_size", -1)
        cc.reset_cache()
    (path,) = list_files(cache_dir)
    stamp = path[:-len("cache")] + "atime"
    assert 0 < int.from_bytes(open(stamp, "rb").read(), "little") <= time.time_ns()
    capped = lru_cache.LRUCache(cache_dir, max_size=1 << 30)
    capped._evict_if_needed(additional_size=0)      # reads every entry's stamp
    assert list_files(cache_dir) == [path]
    capped._evict_if_needed(additional_size=1 << 30)   # and evicts them all
    assert not list_files(cache_dir) and not os.path.exists(stamp)
    _, again = life(cfg, params)       # a first life again
    assert not ahead_span() and list_files(cache_dir) == [path]
