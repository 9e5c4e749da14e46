"""Distributed trace context + fleet-merged timelines (util/tracing.py,
util/timeline.py, the TaskSpec.trace_ctx wire field, and the GCS-side trace
store): epoch-anchored stamps, the bounded ring's drain-cursor accounting,
context adoption across process boundaries, and the end-to-end
submit -> lease -> dispatch -> execute -> result chain for a real task."""

import json
import os
import time

import pytest

import ray_tpu
from ray_tpu.util import timeline, tracing


@pytest.fixture(autouse=True)
def _clean_ring():
    tracing.clear()
    tracing.set_ctx(None)
    yield
    tracing.clear()
    tracing.set_ctx(None)


@pytest.fixture
def traced_cluster(monkeypatch):
    """Cluster with distributed tracing ON via the env knob — set before
    init so worker subprocesses inherit it through their environment."""
    from ray_tpu.core.config import reset_config

    monkeypatch.setenv("RAY_TPU_TRACING_ENABLED", "1")
    reset_config()
    ray_tpu.init(num_cpus=4, resources={"TPU": 8})
    yield ray_tpu
    ray_tpu.shutdown()
    reset_config()


# ------------------------------------------------------------ unit: clock
def test_epoch_anchor_matches_wall_clock_and_is_monotone():
    """Satellite 1: stamps are wall-epoch microseconds (comparable across
    processes on a host), not a process-local perf_counter origin."""
    a = tracing.now_us()
    wall = time.time() * 1e6
    b = tracing.now_us()
    assert abs(a - wall) < 0.5e6, (a, wall)  # same epoch, sub-second agreement
    assert b >= a
    stamps = [tracing.now_us() for _ in range(100)]
    assert stamps == sorted(stamps)


# ------------------------------------------------------- unit: bounded ring
def test_ring_bound_and_drain_cursor_counts_drops(monkeypatch):
    """Satellite 2: the ring holds at most tracing_max_buffer_size spans;
    overflow drops the OLDEST and drain() reports the drop count exactly
    once, even when the overflow happens between two drains."""
    from ray_tpu.core.config import get_config, reset_config

    monkeypatch.setenv("RAY_TPU_TRACING_MAX_BUFFER_SIZE", "8")
    reset_config()
    try:
        assert get_config().tracing_max_buffer_size == 8
        for i in range(5):
            tracing.add_complete(f"s{i}", "test", float(i), 1.0)
        fresh, cursor, dropped = tracing.drain(0)
        assert [e["name"] for e in fresh] == [f"s{i}" for i in range(5)]
        assert cursor == 5 and dropped == 0

        # 12 more: ring keeps the newest 8, so 9 total fall off the left
        # edge (5 already drained ones count via the cursor, 4 undrained
        # ones via the dropped counter -- drain() reports the max so the
        # shipped accounting can never undercount)
        for i in range(5, 17):
            tracing.add_complete(f"s{i}", "test", float(i), 1.0)
        fresh, cursor, dropped = tracing.drain(cursor)
        assert [e["name"] for e in fresh] == [f"s{i}" for i in range(9, 17)]
        assert cursor == 17
        assert dropped == 4, dropped  # s5..s8 overflowed before shipping
        assert len(tracing.get_events()) == 8

        # a cursor from before clear() resyncs instead of skipping forever
        tracing.clear()
        tracing.add_complete("post", "test", 1.0, 1.0)
        fresh, cursor, dropped = tracing.drain(cursor)
        assert [e["name"] for e in fresh] == ["post"] and cursor == 1
    finally:
        reset_config()


# ------------------------------------------------------------- unit: ctx
def test_span_nesting_and_ctx_scope_restore():
    ctx = tracing.start_trace()
    assert ctx[1] == "" and tracing.current_ctx() == ctx
    with tracing.span("outer", "test"):
        mid = tracing.current_ctx()
        assert mid[0] == ctx[0] and mid[1] != ""
        with tracing.span("inner", "test"):
            assert tracing.current_ctx()[1] not in ("", mid[1])
    assert tracing.current_ctx() == ctx  # restored after both exits

    events = {e["name"]: e for e in tracing.get_events()}
    outer, inner = events["outer"], events["inner"]
    assert outer["trace_id"] == inner["trace_id"] == ctx[0]
    assert outer["parent_id"] == ""              # root of the tree
    assert inner["parent_id"] == outer["span_id"]

    # ctx_scope adopts a foreign ctx and restores the previous one;
    # None is a no-op so call sites need no conditional
    with tracing.ctx_scope(("t2", "p2")):
        assert tracing.current_ctx() == ("t2", "p2")
        with tracing.ctx_scope(None):
            assert tracing.current_ctx() == ("t2", "p2")
    assert tracing.current_ctx() == ctx


def test_spans_unattributed_without_ambient_ctx():
    with tracing.span("loose", "test"):
        pass
    (e,) = tracing.get_events()
    assert "trace_id" not in e and "span_id" not in e
    assert e["ph"] == "X" and e["dur"] >= 0


# ------------------------------------------------- unit: timeline helpers
def _mk(name, cat, ts, dur, trace="t1", sid=None, parent="", src=None,
        **args):
    e = {"name": name, "cat": cat, "ph": "X", "ts": ts, "dur": dur,
         "pid": 1, "tid": 1, "trace_id": trace, "span_id": sid or name,
         "parent_id": parent, "args": args}
    if src:
        e["_src"] = src
    return e


def test_apply_offsets_rebases_per_source():
    spans = [_mk("a", "c", 100.0, 1.0, src="n1"),
             _mk("b", "c", 100.0, 1.0, src="n2"),
             _mk("c", "c", 100.0, 1.0)]  # no _src: GCS-local, unshifted
    out = timeline.apply_offsets(spans, {"n1": 50.0, "n2": -25.0})
    assert [s["ts"] for s in out] == [150.0, 75.0, 100.0]
    assert spans[0]["ts"] == 100.0  # copies, originals untouched


def test_merge_chrome_sorts_and_validates():
    spans = [_mk("late", "c", 300.0, 1.0, src="n1"),
             _mk("early", "c", 50.0, 1.0)]
    doc = timeline.merge_chrome(spans, {"n1": -100.0})
    assert [e["name"] for e in doc["traceEvents"]] == ["early", "late"]
    assert timeline.validate_chrome(doc) == []
    # the validator actually catches breakage
    assert timeline.validate_chrome({"traceEvents": "nope"})
    bad = {"traceEvents": [{"name": "x", "ph": "X", "ts": 2.0, "pid": 1,
                            "tid": 1, "dur": -1.0},
                           {"name": "y", "ph": "X", "ts": 1.0, "pid": 1,
                            "tid": 1, "dur": 0.0}]}
    problems = timeline.validate_chrome(bad)
    assert any("dur" in p for p in problems)
    assert any("regresses" in p for p in problems)


def test_validate_chains_detects_broken_parent_links():
    good = [_mk("root", "c", 1.0, 1.0, sid="r"),
            _mk("kid", "c", 2.0, 1.0, sid="k", parent="r", src="n2")]
    orphan = [_mk("kid", "c", 2.0, 1.0, trace="t2", sid="k2",
                  parent="ghost")]
    chains = timeline.validate_chains(good + orphan, ["t1", "t2", "t3"])
    assert chains["t1"]["complete"] and chains["t1"]["processes"] == 2
    assert not chains["t2"]["complete"]
    assert chains["t2"]["missing_parents"] == ["ghost"]
    assert not chains["t3"]["complete"] and chains["t3"]["spans"] == 0


def test_stage_segments_orders_by_stage_then_time():
    tid = "ab" * 8
    spans = [_mk("run", "task_execution", 30.0, 5.0, task_id=tid),
             _mk("sub", "task_submit", 10.0, 1.0, sid="s2", task_id=tid),
             _mk("lease", "task_lease", 12.0, 3.0, sid="s3", task_id=tid),
             _mk("other", "task_submit", 1.0, 1.0, sid="s4",
                 task_id="ff" * 8),
             _mk("misc", "serve_route", 5.0, 1.0, sid="s5", task_id=tid)]
    segs = timeline.stage_segments(spans, tid)
    assert [s[0] for s in segs] == ["task_submit", "task_lease",
                                    "task_execution"]
    assert segs[0][1:] == (10.0, 1.0)


# ------------------------------------------------ e2e: one task, one tree
def test_task_chain_spans_processes_and_stages(traced_cluster):
    """The tentpole acceptance shape, single-task scale: a driver submit
    with a nested child task yields ONE trace whose spans cover all five
    critical-path stages, parent links all resolve, the nested submission
    parents under the outer execution span, and the per-source clock
    offsets are within the 10 ms alignment bound."""
    from ray_tpu.core.api import _global_worker

    @ray_tpu.remote
    def trace_inner(x):
        return x + 1

    @ray_tpu.remote
    def trace_outer(x):
        return ray_tpu.get(trace_inner.remote(x), timeout=30)

    ref = trace_outer.remote(1)
    assert ray_tpu.get(ref, timeout=60) == 2
    task_id = ref.task_id().binary().hex()

    w = _global_worker()
    spans, reply = [], {}
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        w.task_events.flush()
        reply = w.gcs.call("get_trace", {"task_id": task_id}, timeout=10)
        spans = reply.get("spans") or []
        cats = {s.get("cat") for s in spans}
        if set(timeline.STAGE_ORDER) <= cats and len(spans) >= 8:
            break
        time.sleep(0.3)

    cats = {s.get("cat") for s in spans}
    assert set(timeline.STAGE_ORDER) <= cats, (cats, len(spans))

    chain = timeline.validate_chain(spans)
    assert chain["complete"], chain
    assert chain["processes"] >= 2, chain  # driver(+raylet) and worker(s)

    # the outer task owns one span per stage, in causal order
    segs = timeline.stage_segments(spans, task_id)
    assert [s[0] for s in segs] == list(timeline.STAGE_ORDER), segs

    # nested propagation: the child's submit span parents under the outer
    # task's execution span (the worker adopted the spec's ctx)
    exec_span = next(s for s in spans if s.get("cat") == "task_execution"
                     and (s.get("args") or {}).get("task_id") == task_id)
    nested_submits = [s for s in spans if s.get("cat") == "task_submit"
                      and (s.get("args") or {}).get("task_id") != task_id]
    assert nested_submits, "child task submit span missing from the trace"
    assert any(s.get("parent_id") == exec_span["span_id"]
               for s in nested_submits), (exec_span, nested_submits)

    # per-source NTP-style offsets: same host, so alignment must land well
    # inside the 10 ms acceptance bound
    offsets = w.gcs.call("get_span_offsets", {}, timeout=10)
    assert offsets, "no clock offsets reported"
    assert all(abs(v) < 10_000 for v in offsets.values()), offsets

    # the merged document is structurally valid chrome JSON
    doc = timeline.merge_chrome(spans, reply.get("offsets"))
    assert timeline.validate_chrome(doc) == []


def test_gcs_stats_reports_stage_latency(traced_cluster):
    from ray_tpu.core.api import _global_worker

    @ray_tpu.remote
    def stats_probe():
        return 1

    assert ray_tpu.get(stats_probe.remote(), timeout=60) == 1
    w = _global_worker()
    deadline = time.monotonic() + 20
    tr = {}
    while time.monotonic() < deadline:
        w.task_events.flush()
        tr = w.gcs.call("gcs_stats", timeout=10).get("tracing") or {}
        lat = tr.get("stage_latency_us") or {}
        if "task_execution" in lat and "task_submit" in lat:
            break
        time.sleep(0.3)
    assert tr.get("enabled") is True
    lat = tr["stage_latency_us"]
    for stage in ("task_submit", "task_execution"):
        s = lat[stage]
        assert s["count"] >= 1
        assert 0 <= s["p50_us"] <= s["p99_us"]


def test_tracing_default_off_mints_nothing(ray_start_regular):
    """Envelope guard: with the default config no trace ids are minted on
    the hot path -- profile spans still record, but carry no trace_id."""
    assert not tracing.enabled()

    @ray_tpu.remote
    def untraced_noop():
        return 1

    assert ray_tpu.get(untraced_noop.remote(), timeout=60) == 1
    assert all("trace_id" not in e for e in tracing.get_events())


def test_context_less_submit_stamps_nothing(ray_start_regular, monkeypatch):
    """The hot path PR 19 protected: switch off, no ambient context -> the
    spec carries no trace_ctx and no id is minted (one thread-local read)."""
    from ray_tpu.core.api import _global_worker

    minted = []
    real = tracing.new_id
    monkeypatch.setattr(tracing, "new_id",
                        lambda: minted.append(1) or real())

    class _Spec:
        trace_ctx = None

    spec = _Spec()
    assert not tracing.enabled() and tracing.current_ctx() is None
    assert _global_worker()._stamp_trace_ctx(spec) == 0.0
    assert spec.trace_ctx is None and not minted

    @ray_tpu.remote
    def noop():
        return 1

    assert ray_tpu.get(noop.remote(), timeout=60) == 1
    assert not minted
    # ...and with an ambient context the same call stamps it
    with tracing.ctx_scope(("t" * 16, "p" * 16)):
        assert _global_worker()._stamp_trace_ctx(spec) > 0.0
    assert spec.trace_ctx[0] == "t" * 16 and len(minted) == 1


def test_ambient_context_propagates_with_switch_off(ray_start_regular):
    """THE RULE: a context propagates whenever one exists. With default
    settings a task submitted under an ambient context carries it; its
    worker-side `task::` span joins the trace, parented under the submit
    span, and a task IT submits stays in the trace too."""
    assert not tracing.enabled()

    @ray_tpu.remote
    def inner_ctx():
        from ray_tpu.util import tracing as t

        return t.current_ctx()

    @ray_tpu.remote
    def outer_ctx():
        from ray_tpu.util import tracing as t

        return t.current_ctx(), ray_tpu.get(inner_ctx.remote(), timeout=30)

    trace_id = tracing.new_id()
    with tracing.ctx_scope((trace_id, "")):
        ref = outer_ctx.remote()
    got_outer, got_inner = ray_tpu.get(ref, timeout=60)
    assert got_outer[0] == trace_id and got_inner[0] == trace_id

    spans = []
    deadline = time.monotonic() + 20
    while time.monotonic() < deadline:
        spans = [e for e in ray_tpu.timeline()
                 if e.get("trace_id") == trace_id]
        if sum(e["name"].startswith("task::") for e in spans) >= 2:
            break
        time.sleep(0.3)
    by_name = {e["name"]: e for e in spans}
    submit, task = by_name["submit::outer_ctx"], by_name["task::outer_ctx"]
    assert submit["parent_id"] == "" and task["parent_id"] == submit["span_id"]
    assert task["pid"] != submit["pid"]  # it crossed a process boundary
    # the nested submission parents under the outer execution span
    assert by_name["submit::inner_ctx"]["parent_id"] == task["span_id"]
    assert timeline.validate_chain(spans)["complete"], spans


def test_timeline_survives_shutdown_of_a_local_cluster():
    """The head's GCS span ring dies with the cluster: `shutdown()` of the
    process that hosts it keeps `timeline()`'s last result, and
    `timeline()` with no cluster returns that session, worker spans
    included, with the GCS's account of what was lost (nothing)."""
    ray_tpu.init(num_cpus=2)
    try:
        @ray_tpu.remote
        def kept_after_shutdown():
            return 1

        assert ray_tpu.get(kept_after_shutdown.remote(), timeout=60) == 1
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:  # the worker ships on a timer
            if any(e["name"] == "task::kept_after_shutdown"
                   for e in ray_tpu.timeline()):
                break
            time.sleep(0.2)
    finally:
        ray_tpu.shutdown()
    assert not ray_tpu.is_initialized()
    kept = [e for e in ray_tpu.timeline()
            if e["name"] == "task::kept_after_shutdown"]
    assert len(kept) == 1 and kept[0]["pid"] != os.getpid()
    info = ray_tpu.timeline_info()
    assert info["spans_dropped"] == 0 and info["spans_evicted"] == 0
    assert info["spans_buffered"] >= 1


def test_span_yields_its_args_and_names_the_open_span():
    assert tracing.open_span_name() is None
    with tracing.span("outer", "test", a=1) as args:
        assert tracing.open_span_name() == "outer"
        with tracing.span("inner", "test"):
            assert tracing.open_span_name() == "inner"
        assert tracing.open_span_name() == "outer"
        args["b"] = 2  # known only at the end of the block
    assert tracing.open_span_name() is None
    outer = next(e for e in tracing.get_events() if e["name"] == "outer")
    assert outer["args"] == {"a": 1, "b": 2}


def test_otel_hook_keeps_the_spans_own_times_and_ids():
    """The bridge stamps an OTel span with the framework span's own start
    and end (not "now") and carries the trace ids as attributes."""
    from ray_tpu.util.otel import disable_otel_tracing, enable_otel_tracing

    done = []

    class _Span:
        def __init__(self, name, start_time):
            self.name, self.start_time, self.attributes = name, start_time, {}

        def set_attribute(self, k, v):
            self.attributes[k] = v

        def end(self, end_time=None):
            self.end_time = end_time
            done.append(self)

    class _Provider:
        def get_tracer(self, name):
            class _Tracer:
                def start_span(self, name, start_time=None):
                    return _Span(name, start_time)
            return _Tracer()

    enable_otel_tracing(_Provider())
    try:
        tracing.add_complete("old", "test", 1_000_000.0, 2_500.0,
                             trace_id="t1", span_id="s1", parent_id="p1")
    finally:
        disable_otel_tracing()
    (s,) = [d for d in done if d.name == "old"]
    assert s.start_time == 1_000_000_000 and s.end_time == 1_002_500_000
    assert (s.attributes["trace_id"], s.attributes["span_id"],
            s.attributes["parent_id"]) == ("t1", "s1", "p1")


def test_rpc_latency_histogram_exported(ray_start_regular):
    """The central rpc.py instrumentation point: any cluster activity
    populates ray_tpu_rpc_latency_seconds in the Prometheus registry,
    tagged per method -- tracing off included (it is always-on and cheap)."""
    from ray_tpu.util.metrics import export_prometheus

    @ray_tpu.remote
    def rpc_probe():
        return 1

    assert ray_tpu.get(rpc_probe.remote(), timeout=60) == 1
    text = export_prometheus()
    assert "ray_tpu_rpc_latency_seconds_bucket" in text
    assert 'method="' in text
    assert "ray_tpu_rpc_latency_seconds_count" in text


# --------------------------------------------------- flight recorder dump
def test_flight_recorder_dumps_spans_and_metrics(tmp_path, monkeypatch):
    from ray_tpu.core.config import reset_config
    from ray_tpu.util import metrics
    from ray_tpu.util.flight_recorder import (dump_flight_record,
                                              flight_record_path)

    monkeypatch.setenv("RAY_TPU_TRACING_ENABLED", "1")
    reset_config()
    try:
        tracing.add_complete("recent", "test", tracing.now_us() - 1e6, 5.0)
        tracing.add_complete("ancient", "test", tracing.now_us() - 900e6,
                             5.0)
        metrics.get_or_create(
            "counter", "test_flightrec_ctr", "x",
            tag_keys=("k",)).inc(2.0, tags={"k": "v"})
        artifact = str(tmp_path / "storm.json")
        out = dump_flight_record(artifact, ["p99 over budget"],
                                 reason="violations")
        assert out == flight_record_path(artifact)
        with open(out) as f:
            rec = json.load(f)  # tuple-keyed metric tags were stringified
        assert rec["reason"] == "violations"
        assert rec["violations"] == ["p99 over budget"]
        names = [s["name"] for s in rec["spans"]]
        assert "recent" in names and "ancient" not in names
        assert "test_flightrec_ctr" in rec["metrics"]
    finally:
        reset_config()
