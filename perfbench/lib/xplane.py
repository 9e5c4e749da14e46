"""From a profiler trace (`.xplane.pb`) to numbers: device busy and idle
time, the operations that took most of it, the idle gaps named by the
benchmark's span that covered them, and the exposed collective time.

`load` needs jax (it reads the file with `jax.profiler.ProfileData`); the
reduction itself is plain Python over (name, start_ns, duration_ns) rows, so
it is checked on fixed inputs and on a small recorded trace.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
_COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast|ragged-all-to-all")
# "%fusion.3 = bf16[8,128]{1,0:T(8,128)} fusion(...)" -> fusion.3, bf16[8,128]
_HLO = re.compile(r"^%?([\w.\-]+)\s*=\s*(\(?[a-z]+[0-9]*\[[^\]]*\])?")


def find_xplane(trace_dir: str) -> str:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return hits[-1]


def load(path: str) -> Dict[str, Dict[str, List[Event]]]:
    """{plane: {line: events}} for the device planes (their operation and
    module lines) and for the host's threads (the benchmark's spans only)."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    out: Dict[str, Dict[str, List[Event]]] = {}
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        lines = {}
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            ev = [(e.name, float(e.start_ns), float(e.duration_ns))
                  for e in line.events
                  if device or e.name.startswith(SPAN_PREFIX)]
            if ev:
                lines[line.name] = lines.get(line.name, []) + ev
        if lines:
            out[plane.name] = lines
    return out


def op_label(name: str) -> str:
    """A short stable label of a device operation: the instruction's name
    and the shape it yields, without layout and operands."""
    m = _HLO.match(name)
    if m:
        label = m.group(1) + ("_" + m.group(2) if m.group(2) else "")
    else:
        label = name
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", label)[:64]


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def _subtract(a: List[Tuple[float, float]], b: List[Tuple[float, float]]) -> float:
    """Length of the part of `a` (a union) that `b` (a union) does not cover."""
    covered, j = 0.0, 0
    for lo, hi in a:
        while j < len(b) and b[j][1] <= lo:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            covered += min(hi, b[k][1]) - max(lo, b[k][0])
            k += 1
    return _total(a) - covered


def self_times(events: Sequence[Event]) -> List[Tuple[str, float, float, float]]:
    """(name, start, end, self_ns) per event: its duration less the part its
    children cover (a `while` spans the operations of its body)."""
    rows = sorted(((s, -(d), n) for n, s, d in events))
    out, stack = [], []  # stack of [name, start, end, child_ns]
    for s, negd, n in rows:
        e = s - negd
        while stack and stack[-1][2] <= s:
            top = stack.pop()
            out.append((top[0], top[1], top[2], max(0.0, top[2] - top[1] - top[3])))
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([n, s, e, 0.0])
    while stack:
        top = stack.pop()
        out.append((top[0], top[1], top[2], max(0.0, top[2] - top[1] - top[3])))
    return out


def reduce(planes: Dict[str, Dict[str, List[Event]]], top: int = 10) -> dict:
    """busy_s and window_s (averaged over the device planes), top operations
    by self time (summed over chips, seconds), the longest idle gaps with the
    covering span, exposed collective seconds (mean over chips), and the
    median duration of each module (jitted program) in ms."""
    devices = {k: v for k, v in planes.items() if k.startswith("/device:")
               and OPS_LINE in v}
    if not devices:
        raise ValueError(f"no device plane with an {OPS_LINE!r} line in the "
                         f"trace; planes: {sorted(planes)}")
    spans = [ev for k, v in planes.items() if k.startswith("/host:")
             for line in v.values() for ev in line]
    t0 = min(s for v in devices.values() for _, s, _ in v[OPS_LINE])
    t1 = max(s + d for v in devices.values() for _, s, d in v[OPS_LINE])
    busy, exposed, ops, gaps, modules = [], [], {}, [], {}
    for plane in devices.values():
        rows = self_times(plane[OPS_LINE])
        busy_u = union([(s, e) for _, s, e, _ in rows])
        busy.append(_total(busy_u))
        for n, _, _, self_ns in rows:
            ops[op_label(n)] = ops.get(op_label(n), 0.0) + self_ns
        leaves = [(n, s, e) for n, s, e, self_ns in rows
                  if self_ns >= 0.999 * (e - s)]
        coll = union([(s, e) for n, s, e in leaves if _COLLECTIVE.search(n)])
        comp = union([(s, e) for n, s, e in leaves if not _COLLECTIVE.search(n)])
        exposed.append(_subtract(coll, comp))
        edges = [(t0, t0)] + busy_u + [(t1, t1)]
        for (_, a), (b, _) in zip(edges, edges[1:]):
            if b > a:
                gaps.append((a, b))
        for n, _, d in plane.get(MODULES_LINE, []):
            modules.setdefault(re.sub(r"\(.*", "", n), []).append(d)
    gaps.sort(key=lambda g: g[0] - g[1])

    def covering(a, b):
        mid, best = 0.5 * (a + b), None
        for n, s, d in spans:
            if s <= mid <= s + d and (best is None or d < best[1]):
                best = (n, d)  # the innermost span over the gap's middle
        return best[0] if best else "no-span"

    n_dev = len(devices)

    def median(xs):
        xs = sorted(xs)
        return 0.5 * (xs[(len(xs) - 1) // 2] + xs[len(xs) // 2])

    return {
        "devices": n_dev,
        "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(busy) / n_dev / 1e9,
        "exposed_collective_s": sum(exposed) / n_dev / 1e9,
        "device_ops": [[n, v / 1e9] for n, v in sorted(
            ops.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[covering(a, b), (b - a) / 1e9] for a, b in gaps[:top]],
        "module_ms_p50": {n: median(ds) / 1e6 for n, ds in modules.items()},
        "module_calls": {n: len(ds) for n, ds in modules.items()},
    }


def idle_share_pct(reduced: dict) -> float:
    """Share of the traced window in which no operation ran on the device."""
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def reduce_dir(trace_dir: str, rehearsal: bool = False):
    """Reduce the trace under `trace_dir` and remove it. A CPU rehearsal has
    no device plane to reduce: it gets None, and every metric that reads the
    trace then has nothing to read."""
    import shutil

    try:
        return reduce(load(find_xplane(trace_dir)))
    except ValueError:
        if rehearsal:
            return None
        raise
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
