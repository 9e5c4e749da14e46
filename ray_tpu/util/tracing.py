"""Built-in timeline: chrome://tracing events + distributed trace context.

Equivalent of the reference's profile-event timeline
(`src/ray/core_worker/profile_event.h` -> `ray.timeline()`,
`python/ray/_private/state.py:851 chrome_tracing_dump:435`): lightweight
in-process event recording, dumped as chrome trace JSON.

Two properties make multi-process merges meaningful:

- **Epoch anchor.** Timestamps are wall-epoch MICROSECONDS, derived as
  `_epoch_us + (perf_counter() - _t0)`: one `(time.time(), perf_counter())`
  pair captured at import anchors the monotonic clock to the epoch, so
  spans are monotone within a process AND directly comparable across
  processes on one host. Cross-NODE skew is corrected at merge time from
  per-source clock offsets (task_events.py estimates them NTP-style from
  an RPC round-trip to the GCS).

- **Bounded ring.** The in-process buffer is capped
  (`tracing_max_buffer_size`, mirroring `task_events_max_buffer_size`):
  overflow drops the OLDEST spans and counts them; `drain()` hands the
  dropped count to the TaskEventBuffer so it rides the next flush and the
  GCS-side truncation accounting stays honest.

THE RULE: a trace context propagates whenever one exists;
`tracing_enabled` only decides whether a context-less task submit mints
one. A Serve ingress always mints the request's context, so every request
is one trace with default settings; a plain task submitted with no ambient
context and the switch off pays one thread-local read and mints nothing.

Trace context (the distributed half): a thread-local
`(trace_id, parent_span_id)` pair. `span()` records both ids plus its own
fresh span_id on the event and re-parents nested spans under itself;
`ctx_scope()` adopts a context that crossed a process boundary
(TaskSpec.trace_ctx), making ingress -> route -> submit -> raylet lease ->
worker execute -> engine -> result delivery one causal tree under a single
trace_id. For a streamed Serve request the chain is `ingress:: -> route::
-> submit:: -> task:: -> stream:: -> engine.queue / prefill / decode /
stream -> relay::`: `task::` of a streaming method ends when the generator
is RETURNED; the loop that runs it is `stream::` (core/worker.py), the
thread that consumes the engine's tokens emits `engine.stream`
(models/serving.py), and the proxy's loop that writes them `relay::`
(serve/http_proxy.py).

Before `ingress::` / `task::`, a worker's set-up is a chain of its own:
`lease.tpu -> worker.spawn > worker.boot -> actor.create::<Class> >
chip.open, xla.compile`. The raylet records `lease.tpu` (a TPU demand's
arrival -> its worker's `Popen`; `queued_us` for free chips,
`holders_wait_us` for a foreign holder, `pid`, `chips`, `tpu_ids`) and
`worker.spawn` (`Popen` -> that pid's registration handled; `pid`, `chips`);
the worker `worker.boot` (the raylet's spawn stamp -> registration
acknowledged; `imports_us` of it the interpreter and the imports),
`actor.create::<Class>` around the user's constructor (`chips`), `chip.open`
around the call that initialises jax's backends, whoever makes it
(`core/chips.py:time_chip_open`; `platform`, `device_kind`, `devices`,
`granted`), and a program's `xla.compile` spans, whose backend-compile span
says how the persistent cache answered (`cache`, `retrieval_us`:
`record_compiles`). `perfbench/lib/setup_spans.py` reads them all.

THE COST RULE: never a span per token. What a token costs on its way is
counted on a frame that already exists (clock reads and integer adds) and
leaves as arguments of ONE span when its stream ends; a step gets one span
beside its own (`engine.between_steps`), a request three. Set-up spans are
once a worker's life (once a program's, for `xla.compile`): nothing of them
is on a step's, a token's or a task's path. Every one of them has a metric
that reads it; an argument feeds a metric or the reader's choice and checks
of the chip holder, or is an operator's fact that PERF.md section 3 lists as
such (`queued_us`, `holders_wait_us`, `tpu_ids`: how `lease.tpu` splits, and
over which chips). Add none without one of the three.

One clock with the device trace: `span()` also enters
`jax.profiler.TraceAnnotation(name)` once jax is loaded in the process (it
never imports jax itself). Outside a profiler session that is a flag test;
inside one the program's spans sit on the host plane of the same
`.xplane.pb` as the device ops.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from collections import deque
from contextlib import contextmanager
from random import getrandbits as _getrandbits
from typing import Deque, Dict, List, Optional, Tuple

_events: Deque[dict] = deque()
_lock = threading.Lock()
# epoch anchor: one wall/monotonic pair per process. perf_counter gives
# monotonicity (time.time() can step under NTP slew); the epoch term makes
# the absolute values line up across processes.
_t0 = time.perf_counter()
_epoch_us = time.time() * 1e6
_dropped = 0          # ring overflow since the last drain()
_total = 0            # events ever appended (drain cursors index into this)
# observers called with each completed span dict — the OpenTelemetry
# bridge (util/otel.py) and the worker's GCS profile-event shipper hook in
# here (reference: opt-in OTel spans + TaskEventBuffer profile events)
_span_hooks: List = []

_tls = threading.local()
# ring bound, read from the config once per process (0 = not read yet;
# clear() forgets it, so a test that resets the config resets this too)
_limit = 0
# jax.profiler.TraceAnnotation once jax is loaded here, else None
_annotation = None
_compiles_recorded = False


def add_span_hook(fn) -> None:
    with _lock:
        if fn not in _span_hooks:
            _span_hooks.append(fn)


def remove_span_hook(fn) -> None:
    with _lock:
        if fn in _span_hooks:
            _span_hooks.remove(fn)


def _now_us() -> float:
    return _epoch_us + (time.perf_counter() - _t0) * 1e6


def now_us() -> float:
    """Epoch-anchored wall microseconds, monotone within this process."""
    return _now_us()


# --------------------------------------------------------------- trace ctx
def enabled() -> bool:
    """Whether a task submitted with NO ambient context mints a trace of
    its own (default off). An existing context propagates either way."""
    from ray_tpu.core.config import get_config

    return get_config().tracing_enabled


def new_id() -> str:
    """16 hex digits. From the process's PRNG (seeded from the OS, reseeded
    in a forked child), not `os.urandom`: every Serve request mints a
    handful, and a system call each costs microseconds in a sandbox."""
    return "%016x" % _getrandbits(64)


def current_ctx() -> Optional[Tuple[str, str]]:
    """The thread's (trace_id, parent_span_id) or None outside a trace."""
    return getattr(_tls, "ctx", None)


def set_ctx(ctx: Optional[Tuple[str, str]]) -> None:
    _tls.ctx = tuple(ctx) if ctx else None


def start_trace() -> Tuple[str, str]:
    """Begin a new trace on this thread; returns (trace_id, "") — the empty
    parent marks subsequent spans as roots of the tree."""
    ctx = (new_id(), "")
    _tls.ctx = ctx
    return ctx


@contextmanager
def ctx_scope(ctx: Optional[Tuple[str, str]]):
    """Adopt a context that crossed a process/thread boundary (a
    TaskSpec.trace_ctx, a router request's captured ctx) for the duration
    of the block. None is a no-op so call sites need no conditional."""
    if not ctx:
        yield
        return
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = tuple(ctx)
    try:
        yield
    finally:
        _tls.ctx = prev


def _append(event: dict) -> None:
    """Caller must NOT hold _lock. Ring-bounded append + hook fanout."""
    global _dropped, _total, _limit
    if not _limit:
        from ray_tpu.core.config import get_config

        _limit = max(1, get_config().tracing_max_buffer_size)
    with _lock:
        _events.append(event)
        _total += 1
        while len(_events) > _limit:
            _events.popleft()
            _dropped += 1
        # hooks observe completed SPANS only (the OTel bridge reads "dur")
        hooks = list(_span_hooks) if event.get("ph") == "X" else ()
    for h in hooks:
        try:
            h(event)
        except Exception:  # user hook: never let tracing kill the task
            pass


def _trace_annotation():
    """jax.profiler.TraceAnnotation once jax is (fully) loaded, else None."""
    global _annotation
    if _annotation is None and "jax" in sys.modules:
        _annotation = getattr(getattr(sys.modules["jax"], "profiler", None),
                              "TraceAnnotation", None)
    return _annotation


def open_span_name() -> Optional[str]:
    """Name of the innermost `span()` open on this thread, or None."""
    return getattr(_tls, "open", None)


@contextmanager
def span(name: str, category: str = "task", **args):
    """Record the block as one complete span. Yields the span's `args`
    dict: what is only known at the end of the block can be set on it."""
    start = _now_us()
    ctx = getattr(_tls, "ctx", None)
    sid = prev = None
    if ctx is not None:
        sid = new_id()
        prev = ctx
        _tls.ctx = (ctx[0], sid)  # nested spans parent under this one
    outer = getattr(_tls, "open", None)
    _tls.open = name
    ann = _annotation or _trace_annotation()
    if ann is not None:
        ann = ann(name)
        ann.__enter__()
    try:
        yield args
    finally:
        if ann is not None:
            ann.__exit__(None, None, None)
        end = _now_us()
        _tls.open = outer
        if sid is not None:
            _tls.ctx = prev
        event = {
            "name": name, "cat": category, "ph": "X",
            "ts": start, "dur": end - start,
            "pid": os.getpid(), "tid": threading.get_ident() % 100000,
            "args": args,
        }
        if sid is not None:
            event["trace_id"] = ctx[0]
            event["span_id"] = sid
            event["parent_id"] = ctx[1]
        _append(event)


def add_complete(name: str, category: str, start_us: float, dur_us: float,
                 trace_id: Optional[str] = None,
                 span_id: Optional[str] = None,
                 parent_id: Optional[str] = None, **args) -> None:
    """Record a complete ("X") span with explicit timing/ids — for call
    sites that measure a window themselves (raylet queue wait, dispatch
    latency, serve ingress) rather than wrapping a block."""
    event = {
        "name": name, "cat": category, "ph": "X",
        "ts": start_us, "dur": max(0.0, dur_us),
        "pid": os.getpid(), "tid": threading.get_ident() % 100000,
        "args": args,
    }
    if trace_id:
        event["trace_id"] = trace_id
        event["span_id"] = span_id or new_id()
        event["parent_id"] = parent_id or ""
    _append(event)


_compile_args: Dict[str, dict] = {}


def note_compile(fun_name: str, **args) -> None:
    """Facts of the program being traced (call it from inside the jitted
    function `fun_name`: it runs once per trace) that its `xla.compile`
    spans then carry beside `event` and `fun_name`."""
    _compile_args[fun_name] = args


def compile_notes() -> Dict[str, dict]:
    """The noted facts as they stand: what a program lowered on this thread
    just now hands, through `thread_compiles`, to the thread that compiles
    it, while this one goes on to trace the next program of the same name."""
    return dict(_compile_args)


def thread_compiles(notes: Optional[Dict[str, dict]] = None, **args) -> None:
    """From here on this thread's `xla.compile` spans carry `args` (a thread
    that loads programs ahead of their first call: `ahead=True`) and take a
    program's noted facts from `notes` (`compile_notes()` of the thread
    that lowered it). What a compile that raised left behind on this thread
    is dropped, so that it rides no other program's span."""
    _tls.compile_args, _tls.compile_notes = args, notes
    _tls.cache_facts = {}


def record_compiles() -> None:
    """Install, once per process, the `jax.monitoring` listeners that turn
    every program's trace / lower / backend-compile event into an `xla.compile` span
    named by the jitted function (`fun_name`; else the innermost open
    program span), so `ray_tpu timeline` says which step recompiled.
    Called by code that imports jax anyway (the engine, `make_train_step`)
    and, in a worker that holds a chip grant, when the backend has opened
    (`core/chips.py:time_chip_open`), so that a chip holder's every program
    has its spans, the first included.

    The backend-compile span also says how the persistent cache answered:
    `cache` `hit` (with `retrieval_us`, what the read cost), `miss` (looked
    up, not found, compiled) or `off` (no cache directory: never looked up).
    jax reports those as `/jax/compilation_cache/` events on the compiling thread BEFORE the
    backend-compile duration of the same program; they are held per thread
    and dropped at the next program's lowering, so that they never ride
    another program's span."""
    global _compiles_recorded
    if _compiles_recorded:
        return
    _compiles_recorded = True
    from jax import config, monitoring

    def cache_facts() -> dict:
        facts = getattr(_tls, "cache_facts", None)
        if facts is None:
            facts = _tls.cache_facts = {}
        return facts

    def on_event(event: str, **kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            # jax computes a key wherever caching is not switched off; it is
            # a lookup only where a cache directory is set. A miss that is
            # not written (under the cache's thresholds) reports no more
            if config.jax_compilation_cache_dir:
                cache_facts().setdefault("cache", "miss")  # until a hit says so
        elif event == "/jax/compilation_cache/cache_hits":
            cache_facts()["cache"] = "hit"
        elif event == "/jax/compilation_cache/cache_misses":
            cache_facts()["cache"] = "miss"

    def on_duration(event: str, secs: float, **kw) -> None:
        if event == "/jax/compilation_cache/cache_retrieval_time_sec":
            cache_facts()["retrieval_us"] = secs * 1e6
            return
        if not event.startswith("/jax/core/compile/"):
            return
        ctx = getattr(_tls, "ctx", None) or (None, None)
        dur = secs * 1e6
        fun_name = str(kw.get("fun_name") or open_span_name() or "")
        bare = fun_name[4:-1] if fun_name.startswith("jit(") else fun_name
        notes = getattr(_tls, "compile_notes", None)
        span = dict(
            (_compile_args if notes is None else notes).get(bare, {}),
            **getattr(_tls, "compile_args", {}),
            name="xla.compile", category="compile", start_us=_now_us() - dur,
            dur_us=dur, trace_id=ctx[0], parent_id=ctx[1],
            event=event.rsplit("/", 1)[-1], fun_name=fun_name)
        pending = getattr(_tls, "pending_traces", None)
        if pending is None:
            pending = _tls.pending_traces = {}
        if span["event"] == "jaxpr_trace_duration":
            # jax reports a trace for every jitted function it meets INSIDE
            # a program too (each `jnp` call: a thousand for a small model,
            # enough to overflow the ring before a flush), and for the loop
            # conditions it traces while lowering; the program's own is the
            # one named like the module that is lowered next: hold them
            pending[span["fun_name"]] = span
            return
        if span["event"] == "jaxpr_to_mlir_module_duration":
            own = pending.get(bare)
            pending.clear()
            cache_facts().clear()  # a compile that raised left its own behind
            if own is not None:
                add_complete(**own)
        elif span["event"] == "backend_compile_duration":
            facts = cache_facts()
            span.update({"cache": "off", **facts})
            facts.clear()
        add_complete(**span)

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


def get_events() -> List[dict]:
    with _lock:
        return list(_events)


def drain(cursor: int) -> Tuple[List[dict], int, int]:
    """Events appended since `cursor` (a running sequence number), the new
    cursor, and how many of them overflowed the ring before this drain
    could ship them (NOT the raw eviction count — already-drained spans
    falling off the left edge are not a loss). The shipping path
    (TaskEventBuffer) uses this instead of list slicing so a ring overflow
    between flushes can never silently skew the window. A cursor from
    before a clear() (cursor > total) resyncs to the start."""
    global _dropped
    with _lock:
        if cursor > _total:
            cursor = 0  # clear() ran; resync
        start_seq = _total - len(_events)
        skipped = max(0, start_seq - cursor)
        fresh = list(_events)[max(0, cursor - start_seq):]
        _dropped = 0
        return fresh, _total, skipped


def recent_events(window_s: float) -> List[dict]:
    """Spans whose END falls within the last `window_s` seconds — the
    flight-recorder slice dumped next to a failed storm artifact."""
    floor = _now_us() - window_s * 1e6
    with _lock:
        return [e for e in _events
                if e.get("ts", 0) + e.get("dur", 0) >= floor]


def clear() -> None:
    global _dropped, _total, _limit
    with _lock:
        _events.clear()
        _dropped = 0
        _total = 0
        _limit = 0
