"""The program's OWN spans of a serving run, for the per-layer metrics that
read them: `ingress::` / `route::` / `task::handle_request` from Serve and
`engine.queue` / `engine.prefill` / `engine.step` / `engine.wait_device`
from the engine, all recorded by `ray_tpu/util/tracing.py` with default
settings. The readers run in the parent after `ray_tpu.shutdown()`: a
process that hosted the head keeps the session's timeline for that
(`ray_tpu.timeline()`, `ray_tpu.timeline_info()`). A program that keeps
none (one older than these spans) gives None, and so do its readers.

A partial trace is never a number: `window()` is None, and says why, unless
every finished request of the window owns one trace holding all of `NEED`
and the GCS counted no span as dropped or evicted.
"""

from __future__ import annotations

from typing import Dict, List, Optional

NEED = ("ingress", "route", "task", "engine.queue", "engine.prefill")
# a request's ingress span opens after the client's send; the two stamps
# come from two processes' clocks on one host
_CLOCK_SLACK_US = 5e3
_INGRESS_WITHIN_US = 1e6


def _fetch() -> Optional[dict]:
    """{"events", "info"} of the session the parent just shut down."""
    try:
        from ray_tpu.core import api
    except Exception as e:
        return _no(f"ray_tpu.core.api does not import: {e!r}")
    if not hasattr(api, "timeline_info"):
        return _no("this program keeps no timeline after shutdown")
    return {"events": api.timeline(), "info": api.timeline_info()}


def _no(why: str) -> None:
    print(f"[program_spans] no reading: {why}", flush=True)
    return None


def _key(name: str) -> str:
    """`ingress::LLM` -> ingress, `task::handle_request` -> task."""
    return name.split("::", 1)[0]


def by_trace(events: List[dict]) -> Dict[str, Dict[str, dict]]:
    """{trace_id: {key: span}}; of several spans under one key (a retried
    attempt has a second `task::`) the earliest is kept."""
    out: Dict[str, Dict[str, dict]] = {}
    for e in events:
        tid = e.get("trace_id")
        if not tid or e.get("ph") != "X":
            continue
        if e["name"].startswith("task::") and e["name"] != "task::handle_request":
            continue
        spans = out.setdefault(tid, {})
        k = _key(e["name"])
        if k not in spans or e["ts"] < spans[k]["ts"]:
            spans[k] = e
    return out


def match_requests(rows: List[dict], traces: Dict[str, Dict[str, dict]]
                   ) -> Dict[int, Dict[str, dict]]:
    """{request index: its trace}. The load generator sends no request id,
    so requests and streamed traces are paired in time order: a request's
    trace is the first unclaimed one whose ingress opened after its send."""
    cands = sorted((t for t in traces.values()
                    if "ingress" in t
                    and (t.get("route", {}).get("args") or {}).get("stream")),
                   key=lambda t: t["ingress"]["ts"])
    sent = sorted((r for r in rows if r.get("sent_wall")),
                  key=lambda r: r["sent_wall"])
    out, j = {}, 0
    for r in sent:
        t_sent = 1e6 * r["sent_wall"]
        while j < len(cands) and cands[j]["ingress"]["ts"] < t_sent - _CLOCK_SLACK_US:
            j += 1
        if j < len(cands) and cands[j]["ingress"]["ts"] <= t_sent + _INGRESS_WITHIN_US:
            out[r["i"]] = cands[j]
            j += 1
    return out


def _steps(events: List[dict], t0_us: float, t1_us: float) -> List[dict]:
    """`engine.step` spans that start in the window, each with `host_us`:
    its duration less the `engine.wait_device` spans inside it (same
    thread; steps of one thread do not overlap)."""
    threads: Dict[tuple, Dict[str, List[dict]]] = {}
    for e in events:
        if e["name"] in ("engine.step", "engine.wait_device"):
            threads.setdefault((e["pid"], e["tid"]), {}).setdefault(
                e["name"], []).append(e)
    out = []
    for spans in threads.values():
        waits = sorted(spans.get("engine.wait_device", []),
                       key=lambda e: e["ts"])
        j = 0
        for s in sorted(spans.get("engine.step", []), key=lambda e: e["ts"]):
            end, waited = s["ts"] + s["dur"], 0.0
            while j < len(waits) and waits[j]["ts"] < end:
                if waits[j]["ts"] >= s["ts"]:
                    waited += waits[j]["dur"]
                j += 1
            if t0_us <= s["ts"] < t1_us:
                out.append({**s, "host_us": s["dur"] - waited})
    return sorted(out, key=lambda s: s["ts"])


def window(run) -> Optional[dict]:
    """{"traces": one {key: span} per finished request of the window,
    "steps": the window's engine steps}, or None. Read once per run (the
    result rides the run's record). A record that brings `program_spans`
    ({"events", "info"}) is read from that instead of from ray_tpu."""
    if "_program_window" not in run:
        run["_program_window"] = _window(run)
    return run["_program_window"]


def _window(run) -> Optional[dict]:
    if "rows" not in run or "window_rows" not in run:
        return None  # not a serving run
    got = run.get("program_spans") or _fetch()
    if got is None:
        return None
    events, info = got["events"], got["info"]
    lost = {k: info.get(k, 0) for k in ("spans_dropped", "spans_evicted")}
    if any(lost.values()):
        return _no(f"the GCS counted lost spans: {lost}")
    matched = match_requests(run["rows"], by_trace(events))
    finished = [r for r in run["window_rows"] if r.get("ok")]
    traces = [matched[r["i"]] for r in finished if r["i"] in matched
              and all(k in matched[r["i"]] for k in NEED)]
    if not finished or len(traces) != len(finished):
        return _no(f"{len(finished)} requests finished in the window, "
                   f"{len(traces)} of them own a trace with all of {NEED}")
    t0 = 1e6 * run["t_open"]
    steps = _steps(events, t0, t0 + 1e6 * run["seconds"])
    print(f"[program_spans] {len(traces)} complete request traces = the "
          f"window's finished requests; {len(steps)} engine steps; "
          f"{info.get('spans_buffered', len(events))} spans in the GCS, none "
          f"lost", flush=True)
    return {"traces": traces, "steps": steps}


def request_percentile_ms(run, q: float, fn) -> Optional[float]:
    """The `q`th percentile over the window's request traces of `fn(trace)`
    (microseconds), in milliseconds; None when there is no whole reading."""
    from perfbench.lib.stats import percentile

    w = window(run)
    return percentile([fn(t) / 1e3 for t in w["traces"]], q) if w else None
