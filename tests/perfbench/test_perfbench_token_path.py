"""The twelve per-layer metrics of a token's way out of the engine
(`perfbench/lib/token_path.py`): each reader on a fixed span list with the
answer worked out by hand, the rule that a program without these spans (or
a partial trace) gives None and never a number, which cells list them (the
serving cells are read from BENCHMARK.json, not pinned here), and
the serving cell rehearsed on the CPU with `--trace 1`."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib.manifest import Manifest, load_py  # noqa: E402

T_OPEN = 1_000.0          # seconds; spans carry microseconds
NAMES = ["engine.driver_device_wait_share", "engine.driver_lock_wait_share",
         "engine.driver_between_steps_share", "engine.between_steps_ms_p95",
         "engine.token_deliver_lag_ms_p95", "engine.wakes_per_token",
         "serve.stream_report_us_per_token", "serve.relay_us_per_token",
         "serve.first_chunk_lag_ms_p50", "engine.driver_bookkeep_share",
         "engine.stream_lock_us_per_token", "serve.arrive_lag_ms_per_token"]


def _cells():
    """BENCHMARK.json's cells, told apart by their traffic file's driver:
    those served over HTTP (`open_loop_http*`), in the manifest's order, and
    the rest. A cell that a later PR adds joins by an appended entry."""
    man = Manifest(ROOT)
    serving = [w["name"] for w in man.data["workloads"]
               if man.load_traffic(w["traffic"])["driver"].startswith("open_loop_http")]
    return serving, [w["name"] for w in man.data["workloads"]
                     if w["name"] not in serving]


SERVING, TRAINING = _cells()


def _span(name, start_ms, dur_ms, trace=None, pid=2, tid=7, **args):
    """A span `start_ms` after the window opened."""
    e = {"name": name, "ph": "X", "ts": 1e6 * T_OPEN + 1e3 * start_ms,
         "dur": 1e3 * dur_ms, "pid": pid, "tid": tid, "args": args}
    if trace:
        e.update(trace_id=trace, span_id=f"{trace}-{name}", parent_id="")
    return e


def _request(trace, sent_ms, tokens, wakes, lag_us, report_us, fetch_us,
             write_us, first_chunk_ms, new=True, leave_out=()):
    """One request's spans: PR 26's seven and, if `new`, the three of the
    token's way out. Its prefill ends 52 ms after the send; the first chunk
    is on the socket `first_chunk_ms` later."""
    t = sent_ms + 1.0
    spans = [_span("ingress::LLM", t, 500.0, trace, pid=1, call="stream"),
             _span("route::LLM", t + 0.3, 0.2, trace, pid=1, stream=True),
             _span("submit::handle_request", t + 0.3, 0.2, trace, pid=1),
             _span("task::handle_request", t + 1.0, 0.1, trace, tid=9),
             _span("engine.queue", t + 1.0, 10.0, trace, tid=9),
             _span("engine.prefill", t + 11.0, 40.0, trace, tid=9),
             _span("engine.decode", t + 51.0, 300.0, trace, tid=9)]
    prefill_end_us = 1e6 * T_OPEN + 1e3 * (t + 51.0)
    if new:
        spans += [
            _span("engine.stream", t + 1.0, 351.0, trace, tid=9, tokens=tokens,
                  wakes=wakes, deliver_lag_us_sum=lag_us,
                  lock_us_sum=2 * fetch_us),
            _span("stream::handle_request", t + 1.1, 351.0, trace, tid=9,
                  items=tokens, report_us_sum=report_us),
            _span("relay::LLM", t + 0.6, 499.0, trace, pid=1, items=tokens,
                  fetch_us_sum=fetch_us, write_us_sum=write_us,
                  arrive_lag_us_sum=100 * write_us,
                  first_write_ts=prefill_end_us + 1e3 * first_chunk_ms)]
    return [s for s in spans if s["name"].split("::")[0] not in leave_out]


def _row(i, sent_ms, due_s):
    return {"i": i, "sent_wall": T_OPEN + sent_ms / 1e3, "due_s": due_s,
            "ok": True}


def _run(new=True, leave_out=(), gaps=True, info=None):
    """Four requests, one before the window; the driver thread (pid 2, tid
    7): three steps and four gaps that start inside the window, one of each
    before it. `new=False`: the spans a program before PR 36 records."""
    rows = [_row(0, -500.0, -0.5), _row(1, 100.0, 0.1), _row(2, 400.0, 0.4),
            _row(3, 600.0, 0.6)]
    events = (
        _request("warm", -500.0, 99, 999, 99_000, 99_000, 9_000, 9_000, 99.0, new)
        + _request("a", 100.0, 10, 25, 5_000, 1_500, 300, 700, 2.0, new)
        + _request("b", 400.0, 20, 30, 30_000, 2_500, 500, 1_500, 3.0, new,
                   leave_out=leave_out)
        + _request("c", 600.0, 10, 25, 10_000, 2_000, 400, 600, 10.0, new))
    # (start, dur, device waits, lock_wait_us) in ms
    for start, dur, waits, lock in ((-40.0, 30.0, (25.0,), 9_000),
                                    (10.0, 30.0, (24.0,), 1_000),
                                    (42.0, 28.0, (20.0,), 500),
                                    (75.0, 40.0, (10.0, 20.0), 2_500)):
        args = {"prefill_batches": 0, "active": 3}
        if new:
            args.update(lock_wait_us=lock, bookkeep_us=200)
        events.append(_span("engine.step", start, dur, **args))
        t = start + 0.5
        for w in waits:
            events.append(_span("engine.wait_device", t, w))
            t += w
    if new and gaps:
        # (start, dur, slept_us, had_work)
        for start, dur, slept, had in ((-10.0, 20.0, 19_000, False),
                                       (0.0, 10.0, 9_000, False),
                                       (40.0, 2.0, 0, True),
                                       (70.0, 5.0, 0, True),
                                       (115.0, 100.0, 99_000, False)):
            events.append(_span("engine.between_steps", start, dur,
                                slept_us=slept, had_work=had))
    return {"t_open": T_OPEN, "seconds": 1.0, "rows": rows,
            "window_rows": rows[1:],
            "program_spans": {"events": events, "info": info or {
                "spans_dropped": 0, "spans_evicted": 0, "spans_buffered": 80}}}


def _read(name, run):
    return load_py(os.path.join(ROOT, "perfbench", "metrics",
                                name + ".py")).read(run)


@pytest.mark.parametrize("name,expected", [
    # D = steps 30 + 28 + 40 = 98 ms, + gaps less their sleep 1 + 2 + 5 + 1 =
    # 9 ms: 107 ms. Device waits inside the steps: 24 + 20 + (10 + 20) = 74
    ("engine.driver_device_wait_share", 100 * 74 / 107),
    ("engine.driver_lock_wait_share", 100 * 4 / 107),      # 1.0 + 0.5 + 2.5 ms
    ("engine.driver_between_steps_share", 100 * 9 / 107),
    # the gaps that found work waiting: 2.0 and 5.0 ms: 2 + 0.95 x 3
    ("engine.between_steps_ms_p95", 4.85),
    # lag a token: 5/10, 30/20, 10/10 ms = 0.5 1.5 1.0: 1.0 + 0.9 x 0.5
    ("engine.token_deliver_lag_ms_p95", 1.45),
    ("engine.wakes_per_token", 2.0),                       # (25 + 30 + 25) / 40
    ("serve.stream_report_us_per_token", 150.0),           # 6000 / 40
    ("serve.relay_us_per_token", 100.0),   # (300 + 500 + 400 + 700 + 1500 + 600) / 40
    ("serve.first_chunk_lag_ms_p50", 3.0),                 # 2 3 10
    ("engine.driver_bookkeep_share", 100 * 0.6 / 107),     # 200 us a step, 3 steps
    ("engine.stream_lock_us_per_token", 60.0),             # 2 x (300 + 500 + 400) / 40
    ("serve.arrive_lag_ms_per_token", 7.0),     # 100 x (700 + 1500 + 600) us / 40
])
def test_reader_on_a_fixed_span_list(name, expected, capsys):
    assert _read(name, _run()) == pytest.approx(expected, abs=1e-6)
    out = capsys.readouterr().out
    assert "3 steps + 4 gaps" in out and "3 streams" in out
    assert "D = 0.107 s" in out


@pytest.mark.parametrize("how", ["older_program", "dropped", "no_relay",
                                 "no_engine.stream", "no_gaps"])
def test_without_every_span_there_is_no_number(how, capsys):
    """The parent's kinds of spans only (what a program before these spans
    records), a span the GCS lost, a request whose stream left no `relay::`
    or no `engine.stream`, a driver thread without gaps: all twelve give None,
    and the run's output says why, once."""
    if how == "older_program":
        run = _run(new=False)
    elif how == "dropped":
        run = _run(info={"spans_dropped": 1, "spans_evicted": 0})
    elif how == "no_gaps":
        run = _run(gaps=False)
    else:
        run = _run(leave_out=(how[3:],))
    assert [_read(n, run) for n in NAMES] == [None] * len(NAMES)
    out = capsys.readouterr().out
    said = "[program_spans] no reading" if how == "dropped" \
        else "[token_path] no reading"
    assert out.count(said) == 1, out
    # PR 26's readers are not taken down with them
    if how != "dropped":
        assert _read("engine.step_host_ms_p50", run) is not None


@pytest.mark.parametrize("cell", SERVING + TRAINING)
def test_the_serving_cells_list_all_twelve(cell):
    """Each of the twelve names its cells (`workloads`), serving cells only,
    and moves `serve_tokens_per_s`, the one end-to-end metric every serving
    cell reports: without the list they would be read in the chat cell
    only. A cell that comes later joins by an appended entry."""
    man = Manifest(ROOT)
    twelve = {m["name"]: m for m in man.data["per_layer"] if m["name"] in NAMES}
    assert sorted(twelve) == sorted(NAMES)
    listed = {m["name"] for m in man.metrics_for(cell, "per_layer")}
    if cell in TRAINING:
        assert not set(NAMES) & listed
        # and a record that is no serving run reads None without a fetch
        assert [_read(n, {"t_open": 0.0, "seconds": 1.0}) for n in NAMES] == \
            [None] * len(NAMES)
        return
    for n in NAMES:
        assert set(twelve[n]["workloads"]) <= set(SERVING)
        assert twelve[n]["moves"] == "serve_tokens_per_s"
        assert os.path.isfile(os.path.join(ROOT, "perfbench", "metrics", n + ".py"))


def test_the_six_serving_cells_of_pr_52_list_all_twelve():
    man = Manifest(ROOT)
    assert len(SERVING) >= 6 and len(TRAINING) >= 2
    for cell in ("internlm2-serve-chat", "kimi-linear-serve-longgen",
                 "jamba2-serve-chat-burst", "openpangu-serve-longctx",
                 "evabyte-serve-longdoc", "granite4h-serve-ragsessions"):
        assert cell in SERVING
        assert set(NAMES) <= {m["name"] for m in man.metrics_for(cell, "per_layer")}


def test_serving_cell_rehearsal_reports_all_twelve(tmp_path):
    """The toy serving cell through the real command on the CPU with
    `--trace 1`: the driver thread's spans and the three spans a stream
    cross three processes and a shutdown, and all twelve readers find them."""
    cells = load_py(os.path.join(ROOT, "tests", "perfbench",
                                 "test_perfbench_cells.py"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, cells.RUN, "--root",
         cells._throw_away_root(tmp_path, serve="toy-serve-token-path"),
         "--workload", "toy-serve-token-path", "--seed", str(2**31 + 11), "--seconds", "2",
         "--trace", "1", "--cpu-rehearsal"],
        capture_output=True, text=True, timeout=300, env=env, cwd=ROOT)
    assert p.returncode == 10, p.stdout[-3000:] + p.stderr[-3000:]
    rep = cells._would_report(p.stdout)
    assert rep["correct"] is True and rep["failed"] == 0
    assert set(NAMES) <= set(rep["metrics"]), (sorted(rep["metrics"]),
                                               p.stdout[-2000:])
    # the replica timed its own trace (`trace_between`), inside the window
    ran = next(l for l in p.stdout.splitlines() if "[trace] the profiler ran" in l)
    started, stopped, a, b, written = map(float, re.findall(r"(-?[\d.]+)s", ran))
    assert (a, b) == (0.5, 1.5) and a <= started < b <= stopped < b + 0.5 <= 2.0
    assert stopped <= written
    said = next(l for l in p.stdout.splitlines() if "[token_path]" in l)
    assert f"{rep['attempted']} streams with all of" in said
    m = {n: rep["metrics"][n]["value"] for n in NAMES}
    assert all(v >= 0 for v in m.values()), m
    shares = [m[n] for n in NAMES[:3] + ["engine.driver_bookkeep_share"]]
    assert all(0 <= s <= 100 for s in shares) and sum(shares) <= 100.0 + 1e-6
    # about one wake a token at least (a token found waiting on the pass
    # that follows a yield took none: this toy steps in under a millisecond)
    assert m["engine.wakes_per_token"] > 0.5
    assert json.dumps(m)   # plain numbers
