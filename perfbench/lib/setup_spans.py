"""The program's OWN spans of a run's set-up, for the per-layer metrics that
split `setup_s`: `lease.tpu` and `worker.spawn` from the raylet, `worker.boot`,
`actor.create::<Class>` and `chip.open` from the process that holds the
chips, and that process's `xla.compile` spans with how the persistent cache
answered (`ray_tpu/util/tracing.py`; all recorded once in a
worker's life, before the window opens). Read in the parent after
`ray_tpu.shutdown()`, from the timeline `lib/program_spans.py` reads.

The chip holder is the process whose `chip.open` span names the run's
platform (of several, the one with the pid the run's device report gave).
A partial trace is never a number: `reading()` is None, and says why once,
if the GCS counted a span as dropped or evicted or a stage's span is
missing (as in a program older than these spans).
"""

from __future__ import annotations

from typing import Dict, List, Optional

from perfbench.lib.program_spans import _fetch

STAGES = ("lease.tpu", "worker.spawn", "worker.boot", "chip.open")
# the raylet's spans name the worker in `args.pid`; the rest are the worker's
_RAYLETS, _WORKERS = STAGES[:2], ("worker.boot", "actor.create")
_EVENTS = {"jaxpr_trace_duration": "trace",
           "jaxpr_to_mlir_module_duration": "lower",
           "backend_compile_duration": "backend"}


def _no(why: str) -> None:
    print(f"[setup_spans] no reading: {why}", flush=True)
    return None


def reading(run) -> Optional[dict]:
    """{"holder": pid, one span per stage under its name ("actor.create" for
    a serving run's replica), "compiles": what `compiles()` gives}, or None.
    Read once per run. A record that brings `program_spans` ({"events",
    "info"}) is read from that."""
    if "_setup_spans" not in run:
        run["_setup_spans"] = _reading(run)
    return run["_setup_spans"]


def _reading(run) -> Optional[dict]:
    if "device" not in run or "t_open" not in run:
        return None
    got = run.get("program_spans") or _fetch()
    if got is None:
        return None
    events, info = got["events"], got["info"]
    lost = {k: info.get(k, 0) for k in ("spans_dropped", "spans_evicted")}
    if any(lost.values()):
        return _no(f"the GCS counted lost spans: {lost}")
    spans = [e for e in events if e.get("ph") == "X"]
    dev = run["device"]
    opened = [e for e in spans if e["name"] == "chip.open"
              and e["args"].get("platform") == dev["platform"]]
    if len(opened) > 1:
        opened = [e for e in opened if e["pid"] == dev.get("pid")]
    if len(opened) != 1:
        return _no(f"{len(opened)} `chip.open` spans name the run's "
                   f"{dev['platform']!r} (pid {dev.get('pid')}): no chip holder "
                   f"to read (a program older than these spans records none)")
    holder = opened[0]["pid"]
    out: Dict[str, object] = {"holder": holder, "chip.open": opened[0]}
    for e in spans:
        key = e["name"].split("::", 1)[0]
        if (key in _WORKERS and e["pid"] == holder) or \
                (key in _RAYLETS and e["args"].get("pid") == holder):
            out.setdefault(key, e)
    need = STAGES + (("actor.create",) if "rows" in run else ())
    missing = [k for k in need if k not in out]
    if missing:
        return _no(f"the chip holder (pid {holder}) has no {missing} span")
    for k, arg in (*((k, "chips") for k in _RAYLETS), ("chip.open", "granted")):
        if out[k]["args"][arg] != dev["count"]:
            return _no(f"`{k}` of pid {holder} names {out[k]['args'][arg]} "
                       f"chips, the run's device report {dev['count']}")
    t_open = 1e6 * run["t_open"]
    # `record_compiles` keeps a program's OWN trace span alone (a function
    # traced inside a program makes none), so each event is a plain sum
    by_event: Dict[str, List[dict]] = {k: [] for k in _EVENTS.values()}
    for e in spans:
        if e["name"] == "xla.compile" and e["pid"] == holder \
                and e["ts"] < t_open and e["args"].get("event") in _EVENTS:
            by_event[_EVENTS[e["args"]["event"]]].append(e)
    out["compiles"] = by_event
    _say(run, out)
    return out


def compiles(run) -> Optional[Dict[str, List[dict]]]:
    """{"trace" | "lower" | "backend": the chip holder's spans of that event
    that began before the window}. The program records a trace span for a
    program's OWN trace alone (`record_compiles`), so no function traced
    inside a program is counted twice."""
    r = reading(run)
    return r["compiles"] if r else None


def stage_s(run, stage: str, arg: Optional[str] = None) -> Optional[float]:
    """Seconds of the holder's `stage` span (or of its argument `arg`, which
    is in microseconds); None when there is no whole reading or the run has
    no such stage."""
    r = reading(run)
    if r is None or stage not in r:
        return None
    e = r[stage]
    return (e["args"][arg] if arg else e["dur"]) / 1e6


def _say(run, r) -> None:
    """Once a run: how the stages tile the outside reading `t_device - t_ask`
    (`worker.spawn_to_device_s`), and what the constructor and the compiles
    held."""
    lease, spawn, boot, opened = (r[k] for k in STAGES)
    s = lambda us: f"{us / 1e6:.2f}"
    end = lambda e: e["ts"] + e["dur"]
    gap = opened["ts"] - end(spawn)
    line = (f"[setup_spans] chip holder pid {r['holder']} ({opened['args']['devices']} "
            f"{opened['args']['device_kind']} of {opened['args']['granted']} granted): "
            f"lease.tpu {s(lease['dur'])} s (queued {s(lease['args']['queued_us'])}, "
            f"foreign holders {s(lease['args']['holders_wait_us'])}, chips "
            f"{lease['args']['tpu_ids']}) + worker.spawn {s(spawn['dur'])} (boot "
            f"{s(boot['dur'])}, imports {s(boot['args']['imports_us'])} of it) "
            f"+ registered -> chip.open {s(gap)}"
            + (f" (the constructor began {s(r['actor.create']['ts'] - end(spawn))} in)"
               if "actor.create" in r else "") + f" + chip.open {s(opened['dur'])}")
    if "t_ask" in run and "t_device" in run:
        outside = 1e6 * (run["t_device"] - run["t_ask"])
        tiled = lease["dur"] + spawn["dur"] + gap + opened["dur"]
        line += (f" = {s(tiled)} of the outside {s(outside)}; remainder "
                 f"{s(outside - tiled)}: ask -> the demand's arrival "
                 f"{s(lease['ts'] - 1e6 * run['t_ask'])}, chip.open's end -> "
                 f"first device {s(1e6 * run['t_device'] - end(opened))}")
    print(line, flush=True)
    c = r["compiles"]
    by = lambda ans: [e for e in c["backend"] if e["args"].get("cache") == ans]
    hits, misses, off = by("hit"), by("miss"), by("off")
    line = "[setup_spans] "
    if "actor.create" in r:
        a = r["actor.create"]
        line += f"{a['name']} {s(a['dur'])} s; "
    line += (f"{len(c['lower'])} programs before the window: trace + lower "
             f"{s(sum(e['dur'] for e in c['trace'] + c['lower']))} s, {len(hits)} "
             f"cache hits read in {s(sum(e['args'].get('retrieval_us', 0) for e in hits))}"
             f", {len(misses)} misses and {len(off)} uncached compiled in "
             f"{s(sum(e['dur'] for e in misses + off))}")
    print(line, flush=True)
    slow = sorted(hits, key=lambda e: -e["args"].get("retrieval_us", 0))[:5]
    if slow:
        print("[setup_spans] longest cache reads: " + ", ".join(
            f"{e['args']['fun_name']} {s(e['args'].get('retrieval_us', 0))}"
            for e in slow), flush=True)
    if misses:
        print("[setup_spans] missed the cache: " + ", ".join(
            f"{e['args']['fun_name']} {s(e['dur'])}" for e in misses[:12])
            + (f" and {len(misses) - 12} more" if len(misses) > 12 else ""),
            flush=True)
