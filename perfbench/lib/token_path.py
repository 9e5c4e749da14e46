"""A token's way out of the engine, read from the program's own spans: the
driver thread's time (`engine.step` with its `engine.wait_device` children
and `lock_wait_us`, `engine.between_steps` with `slept_us`) and the three
spans a stream (`engine.stream` from the thread that consumes the engine's
generator, `stream::handle_request` from the replica's loop that reports
each item, `relay::<deployment>` from the HTTP proxy's loop that writes
it). Twelve per-layer metrics read this one reduction.

It builds on `program_spans.window(run)`: the same window (requests due in
it and finished, steps that start in it), and the same rule that a partial
trace is never a number. A program without these spans (one older than
them) gives None for all twelve, and the run's output says so once.

The driver thread's working time has ONE denominator,
D = sum(`engine.step`.dur) + sum(`engine.between_steps`.dur - `slept_us`)
over the spans that start in the window: the sleep with nothing to do is
no cost. It splits into the device waits inside the steps, the waits to
enter the engine's lock (`lock_wait_us`), what passed between two steps,
and the rest (the driver's own dispatch and bookkeeping).
"""

from __future__ import annotations

from typing import Optional

from perfbench.lib import program_spans
from perfbench.lib.stats import percentile

STREAM_KEYS = ("engine.stream", "stream", "relay")


def _no(why: str) -> None:
    print(f"[token_path] no reading: {why}", flush=True)
    return None


def reading(run) -> Optional[dict]:
    """{"driver": the four sums in microseconds and the gaps that had work,
    "streams": one {key: args} per finished request of the window}, or None.
    Read once per run."""
    if "_token_path" not in run:
        run["_token_path"] = _reading(run)
    return run["_token_path"]


def _reading(run) -> Optional[dict]:
    w = program_spans.window(run)
    if not w:
        return None  # `program_spans` has said why
    got = run.get("program_spans") or program_spans._fetch()
    t0 = 1e6 * run["t_open"]
    t1 = t0 + 1e6 * run["seconds"]
    gaps = [e for e in got["events"] if e["name"] == "engine.between_steps"
            and e.get("ph") == "X" and t0 <= e["ts"] < t1]
    steps = w["steps"]
    counted = [s for s in steps if "lock_wait_us" in (s.get("args") or {})]
    whole = [t for t in w["traces"] if all(k in t for k in STREAM_KEYS)]
    if not gaps or not steps or len(counted) != len(steps) \
            or len(whole) != len(w["traces"]):
        return _no(
            f"{len(gaps)} engine.between_steps spans start in the window, "
            f"{len(counted)} of its {len(steps)} steps carry lock_wait_us, "
            f"{len(whole)} of its {len(w['traces'])} request traces hold all "
            f"of {STREAM_KEYS} (a program older than these spans has none)")
    worked = [g["dur"] - g["args"]["slept_us"] for g in gaps]
    driver = {
        "step_us": sum(s["dur"] for s in steps),
        "device_wait_us": sum(s["dur"] - s["host_us"] for s in steps),
        "lock_wait_us": sum(s["args"]["lock_wait_us"] for s in steps),
        "bookkeep_us": sum(s["args"].get("bookkeep_us", 0) for s in steps),
        "between_us": sum(worked),
        "span_us": sum(s["dur"] for s in steps) + sum(g["dur"] for g in gaps),
        "gaps_with_work_us": [d for g, d in zip(gaps, worked)
                              if g["args"].get("had_work")],
    }
    driver["D_us"] = driver["step_us"] + driver["between_us"]
    streams = [{**{k: t[k]["args"] for k in STREAM_KEYS},
                "prefill_end_us": t["engine.prefill"]["ts"]
                + t["engine.prefill"]["dur"]} for t in w["traces"]]
    print(f"[token_path] driver thread: {len(steps)} steps + {len(gaps)} gaps "
          f"span {driver['span_us'] / 1e6:.3f} s of the {run['seconds']:g} s "
          f"window, D = {driver['D_us'] / 1e6:.3f} s; {len(streams)} streams "
          f"with all of {STREAM_KEYS}", flush=True)
    return {"driver": driver, "streams": streams}


def _per_item(streams: list, key: str, item_key: str, *sum_keys: str):
    """Summed `sum_keys` of every stream's `key` span over its summed
    `item_key`; None where nothing was counted."""
    items = sum(s[key][item_key] for s in streams)
    if items <= 0:
        return None
    return sum(s[key][k] for s in streams for k in sum_keys) / items


def driver_share(run, part: str) -> Optional[float]:
    """`part` of the driver thread's working time D, in percent."""
    r = reading(run)
    if not r or r["driver"]["D_us"] <= 0:
        return None
    return 100.0 * r["driver"][part] / r["driver"]["D_us"]


def per_item(run, key: str, item_key: str, *sum_keys: str) -> Optional[float]:
    """Over the window's streams: the summed `sum_keys` of the `key` span
    divided by its summed `item_key` (attempts or microseconds per item)."""
    r = reading(run)
    return _per_item(r["streams"], key, item_key, *sum_keys) if r else None


def stream_percentile(run, q: float, fn) -> Optional[float]:
    """The `q`th percentile over the window's streams of `fn(stream)`
    (None leaves a stream out)."""
    r = reading(run)
    values = [v for v in map(fn, r["streams"]) if v is not None] if r else []
    return percentile(values, q) if values else None
