"""The seven per-layer metrics that read the program's own spans: each
reader on a fixed span list with hand-worked answers, the rule that a
partial trace is never a number, and the serving cell rehearsed on the CPU
with `--trace 1` and default settings."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import program_spans  # noqa: E402
from perfbench.lib.manifest import Manifest, load_py  # noqa: E402

T_OPEN = 1_000.0          # seconds; spans carry microseconds
NAMES = ["serve.proxy_ms_p50", "serve.router_ms_p50",
         "serve.replica_queue_ms_p50", "engine.queue_ms_p95",
         "engine.prefill_ms_p50", "engine.step_host_ms_p50",
         "engine.prefill_steps_share"]


def _span(name, start_ms, dur_ms, trace=None, pid=2, tid=7, **args):
    """A span `start_ms` after the window opened."""
    e = {"name": name, "ph": "X", "ts": 1e6 * T_OPEN + 1e3 * start_ms,
         "dur": 1e3 * dur_ms, "pid": pid, "tid": tid, "args": args}
    if trace:
        e.update(trace_id=trace, span_id=f"{trace}-{name}", parent_id="")
    return e


def _request(trace, sent_ms, proxy, router, mailbox, queue, prefill,
             leave_out=()):
    """One request's spans; ingress opens 1 ms after the client's send."""
    t = sent_ms + 1.0
    spans = [_span("ingress::LLM", t, 500.0, trace, pid=1, call="stream"),
             _span("route::LLM", t + proxy, router, trace, pid=1, stream=True),
             _span("submit::handle_request", t + proxy, router, trace, pid=1)]
    t += proxy + router + mailbox
    spans += [_span("task::handle_request", t, 0.1, trace),
              _span("engine.queue", t + 0.2, queue, trace),
              _span("engine.prefill", t + 0.2 + queue, prefill, trace),
              _span("engine.decode", t + 0.2 + queue + prefill, 300.0, trace)]
    return [s for s in spans if s["name"].split("::")[0] not in leave_out]


def _row(i, sent_ms, due_s, ok=True):
    return {"i": i, "sent_wall": T_OPEN + sent_ms / 1e3, "due_s": due_s,
            "ok": ok}


def _run(leave_out=(), info=None):
    """Four requests: one before the window (due -0.5 s), three inside;
    five engine steps, one of them before the window opened."""
    rows = [_row(0, -500.0, -0.5), _row(1, 100.0, 0.1), _row(2, 400.0, 0.4),
            _row(3, 900.0, 0.9)]
    events = (
        _request("warm", -500.0, 9.0, 9.0, 9.0, 99.0, 99.0)
        + _request("a", 100.0, 0.30, 0.10, 0.50, 10.0, 40.0)
        + _request("b", 400.0, 0.50, 0.20, 0.70, 20.0, 44.0,
                   leave_out=leave_out)
        + _request("c", 900.0, 0.40, 0.30, 0.60, 30.0, 42.0)
        # a unary call (info, stats) is no request of the load
        + [_span("ingress::LLM", 150.0, 2.0, "u", pid=1, call="info"),
           _span("route::LLM", 150.1, 0.1, "u", pid=1)])
    for start, dur, waits, batches in ((-40.0, 30.0, (20.0,), 0),
                                       (10.0, 30.0, (28.0,), 0),
                                       (40.0, 44.0, (12.0, 30.5), 1),
                                       (90.0, 30.0, (29.0,), 0),
                                       (120.0, 31.0, (28.5,), 2)):
        events.append(_span("engine.step", start, dur,
                            prefill_batches=batches, active=3))
        t = start + 0.5
        for w in waits:
            events.append(_span("engine.wait_device", t, w))
            t += w
    # a wait on another thread is no part of these steps
    events.append(_span("engine.wait_device", 15.0, 5.0, tid=8))
    return {"t_open": T_OPEN, "seconds": 1.0, "rows": rows,
            "window_rows": rows[1:],
            "program_spans": {"events": events, "info": info or {
                "spans_dropped": 0, "spans_evicted": 0, "spans_buffered": 60}}}


def _read(name, run):
    return load_py(os.path.join(ROOT, "perfbench", "metrics",
                                name + ".py")).read(run)


@pytest.mark.parametrize("name,expected", [
    ("serve.proxy_ms_p50", 0.40),           # 0.30 0.50 0.40
    ("serve.router_ms_p50", 0.20),          # 0.10 0.20 0.30
    ("serve.replica_queue_ms_p50", 0.60),   # 0.50 0.70 0.60
    ("engine.queue_ms_p95", 29.0),          # 10 20 30: 20 + 0.9 x 10
    ("engine.prefill_ms_p50", 42.0),        # 40 44 42
    # step - its waits, the four steps of the window: 2.0 1.5 1.0 2.5
    ("engine.step_host_ms_p50", 1.75),
    ("engine.prefill_steps_share", 50.0),   # two of the four
])
def test_reader_on_a_fixed_span_list(name, expected, capsys):
    assert _read(name, _run()) == pytest.approx(expected, abs=1e-6)
    assert "3 complete request traces" in capsys.readouterr().out


def test_requests_are_matched_to_traces_in_time_order():
    run = _run()
    traces = program_spans.by_trace(run["program_spans"]["events"])
    matched = program_spans.match_requests(run["rows"], traces)
    assert {i: t["ingress"]["trace_id"] for i, t in matched.items()} == \
        {0: "warm", 1: "a", 2: "b", 3: "c"}   # the unary call "u" is no one's
    # a request whose ingress never opened (refused at the socket) takes no
    # other request's trace
    rows = run["rows"] + [_row(4, 5_000.0, 5.0)]
    assert 4 not in program_spans.match_requests(rows, traces)


@pytest.mark.parametrize("how", ["task", "engine.prefill", "dropped", "evicted",
                                 "unfinished", "old_program"])
def test_a_partial_trace_is_never_a_number(how, capsys, monkeypatch):
    """Window requests and complete traces differ in number, or the GCS
    lost spans: every reader gives None, and the run's output says why."""
    if how in ("task", "engine.prefill"):
        run = _run(leave_out=(how,))
    elif how in ("dropped", "evicted"):
        run = _run(info={"spans_dropped": int(how == "dropped"),
                         "spans_evicted": int(how == "evicted")})
    elif how == "unfinished":
        run = _run()
        run["window_rows"] = []
    else:  # a program older than these spans keeps nothing after shutdown
        import ray_tpu.core.api as api

        run = _run()
        del run["program_spans"]
        monkeypatch.delattr(api, "timeline_info")
    assert [_read(n, run) for n in NAMES] == [None] * len(NAMES)
    out = capsys.readouterr().out
    assert out.count("[program_spans] no reading") == 1   # read once per run


def test_train_cells_do_not_load_them():
    man = Manifest(ROOT)
    serve = {m["name"] for m in man.metrics_for("internlm2-serve-chat",
                                                "per_layer")}
    assert set(NAMES) <= serve
    for cell in ("mistral7b-train-1chip", "mistral7b-train-4chip"):
        assert not set(NAMES) & {m["name"]
                                 for m in man.metrics_for(cell, "per_layer")}
    # and a record that is no serving run reads None without a fetch
    assert [_read(n, {"t_open": 0.0, "seconds": 1.0}) for n in NAMES] == \
        [None] * len(NAMES)


def test_serving_cell_rehearsal_reports_all_seven(tmp_path):
    """The toy serving cell through the real command on the CPU with
    `--trace 1` and default environment: the replica's engine spans cross
    three processes and a shutdown, and all seven readers find them."""
    cells = load_py(os.path.join(ROOT, "tests", "perfbench",
                                 "test_perfbench_cells.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("RAY_TPU_TRACING_ENABLED", None)
    p = subprocess.run(
        [sys.executable, cells.RUN, "--root",
         cells._throw_away_root(tmp_path, serve="toy-serve-spans"),
         "--workload", "toy-serve-spans", "--seed", str(2**31 + 7), "--seconds", "2",
         "--trace", "1", "--cpu-rehearsal"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert p.returncode == 10, p.stdout[-3000:] + p.stderr[-3000:]
    rep = cells._would_report(p.stdout)
    assert rep["correct"] is True and rep["failed"] == 0
    assert set(NAMES) <= set(rep["metrics"]), (sorted(rep["metrics"]),
                                               p.stdout[-2000:])
    said = next(l for l in p.stdout.splitlines() if "[program_spans]" in l)
    assert f"{rep['attempted']} complete request traces" in said
    m = {n: rep["metrics"][n]["value"] for n in NAMES}
    assert all(v >= 0 for v in m.values()), m
    assert 0 < m["engine.prefill_steps_share"] <= 100
    assert m["engine.prefill_ms_p50"] > m["engine.step_host_ms_p50"] > 0
