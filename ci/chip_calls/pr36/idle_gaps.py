"""Read one kept `.xplane.pb` of a serving cell by hand, with ALL of the
program's host spans (`perfbench/lib/xplane.py:load` keeps `bench.*` only):
the device's idle gaps, longest first, each named by the PROGRAM's innermost
span over its middle and split by the innermost program span over every part
of it. The program's spans on the host plane are `tracing.span()` blocks
(`engine.step`, `engine.between_steps`, `engine.wait_device`,
`engine.prefill_dispatch`, `task::...`; the spans recorded with
`add_complete`, `engine.stream` among them, are not annotations and are not
there); `bench.*` are the benchmark's and are listed beside them, never
instead.

    JAX_PLATFORMS=cpu python3 ci/chip_calls/pr36/idle_gaps.py <file.xplane.pb> [out.json]
"""

import json
import sys


# what `ray_tpu/util/tracing.py:span()` blocks are named (the XLA runtime's own
# host events carry `::` too: `tpu::System::Execute`)
_PROGRAM = ("engine.", "task::", "xla.compile")


def _program(name: str) -> bool:
    return name.startswith(_PROGRAM)


def _innermost(spans, t):
    best = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (n, s, e)
    return best


def _split(spans, a, b):
    """{innermost span name: ns} over [a, b], cut at every span edge."""
    edges = sorted({a, b, *(x for _, s, e in spans for x in (s, e) if a < x < b)})
    out = {}
    for lo, hi in zip(edges, edges[1:]):
        inner = _innermost(spans, 0.5 * (lo + hi))
        name = inner[0] if inner else "no-span"
        out[name] = out.get(name, 0.0) + hi - lo
    return out


def main(path: str, out_path: str = "") -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops, program, bench = [], [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(float(e.start_ns), float(e.start_ns + e.duration_ns))
                            for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    row = (e.name, float(e.start_ns),
                           float(e.start_ns + e.duration_ns))
                    if _program(e.name):
                        program.append(row)
                    elif e.name.startswith("bench."):
                        bench.append(row)
    if not ops:
        raise SystemExit("no device plane with an 'XLA Ops' line in the trace")
    ops.sort()
    busy = []
    for a, b in ops:
        if busy and a <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], b)
        else:
            busy.append([a, b])
    t0, t1 = busy[0][0], busy[-1][1]
    gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])),
                  reverse=True)
    names = {}
    for n, _, _ in program:
        names[n] = names.get(n, 0) + 1
    rows = []
    for d, a, b in gaps[:10]:
        mid = 0.5 * (a + b)
        inner, outer = _innermost(program, mid), _innermost(bench, mid)
        rows.append({
            "gap_ms": d / 1e6, "at_ms": (a - t0) / 1e6,
            "program_span": inner[0] if inner else "no-span",
            "bench_span": outer[0] if outer else "no-span",
            "split_ms": {n: v / 1e6 for n, v in sorted(
                _split(program, a, b).items(), key=lambda kv: -kv[1])}})
    over_5ms = [(d, a, b) for d, a, b in gaps if d > 5e6]
    bare = [d / 1e6 for d, a, b in over_5ms
            if _innermost(program, 0.5 * (a + b)) is None]
    idle_by_span = {}
    for d, a, b in gaps:
        if d < 1e5:   # the ~us between two operations of one program: not idle
            continue
        for n, v in _split(program, a, b).items():
            idle_by_span[n] = idle_by_span.get(n, 0.0) + v
    out = {
        "file": path, "window_ms": (t1 - t0) / 1e6,
        "busy_ms": sum(b - a for a, b in busy) / 1e6,
        "idle_share_pct": 100.0 * (1 - sum(b - a for a, b in busy) / (t1 - t0)),
        "program_spans_on_the_host_plane": names,
        "bench_spans_on_the_host_plane": len(bench),
        "gaps_over_5ms": len(over_5ms),
        "gaps_over_5ms_under_no_program_span_ms": bare,
        "idle_ms_in_gaps_over_100us_by_innermost_program_span": {n: v / 1e6 for n, v in sorted(
            idle_by_span.items(), key=lambda kv: -kv[1])},
        "ten_longest_idle_gaps": rows}
    text = json.dumps(out, indent=1)
    print(text)
    if out_path:
        with open(out_path, "w") as f:
            f.write(text + "\n")
    return out


if __name__ == "__main__":
    main(*sys.argv[1:3])
