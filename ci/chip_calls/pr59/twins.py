"""Every matmul fusion of the layers' scan bodies beside its twins, from a
compiled text.

For each `while` body of the four-chip training step (PR 54's `cover.py`
finds them) and each of its matmul fusions, in schedule order: the GFLOP of
its convolution (2 x the output's elements x the dimension it sums over,
from the operand shapes and `dim_labels` in the fused computation), XLA's
`estimated_cycles` in ms at the v5e's 1.5 GHz, what share of the 197 TFLOP/s
bf16 peak that is, the tiler's `output_window_bounds`, and the memory space
of each operand as the fused computation's parameters state it (`S(1)` is
fast memory; no `S(n)` is HBM). An operand that is the result of a
`copy-done` whose `copy-start` took a `collective-permute-done` is marked
`EVICTED`: it arrived in fast memory and the compiler's memory-space
assignment moved it to HBM. The tiler's window follows the operands' memory
space, so TWINS (products of equal GFLOP and output shape) whose estimates
are more than 10% apart are flagged: the slower one is paying for an operand
the faster one has nearer. Needs no chip and no jax.

    python ci/chip_calls/pr59/twins.py <compiled.txt> [--json]
    python ci/chip_calls/pr59/twins.py --compile <checkout> <out.txt> [layers]
    python ci/chip_calls/pr59/twins.py <compiled.txt> --excerpt <small.txt>

`--excerpt` writes what this reading (and `tests/test_chip_compile.py`'s case
of it) needs of a compiled text and no more, a hundredth of it: of the two
scan bodies the matmul fusions, the permutes and the copies, of the fused
computations they call the parameters and the convolution with its operands,
every line without its `metadata` and with `backend_config` cut to the
window and the cycles. The kept texts under `tests/compiled_text/` are such.
"""

from __future__ import annotations

import json
import math
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from ci.chip_calls.pr54 import cover as base  # noqa: E402

PEAK_TFLOPS = 197.0   # one v5e chip, bf16 (Google Cloud documentation, "TPU v5e")
APART = 0.10          # twins further apart than this are flagged
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_CONV = re.compile(r"= \w+\[([\d,]*)\]\S* convolution\(%([\w.\-]+), %([\w.\-]+)\), "
                   r"dim_labels=(\w+)_(\w+)->")
_SPACE = re.compile(r"S\((\d+)\)\}")


def _dims(line: str) -> list:
    return [int(d) for d in re.search(r"= \(?\w+\[([\d,]*)\]", line).group(1).split(",") if d]


def gflop(comps: dict, line: str):
    """GFLOP of the convolution a matmul fusion holds, in its own computation
    or in one that calls on: the output's elements times the lhs dimension
    labelled `f` (the one it sums over), twice."""
    called = _CALLS.search(line)
    todo = [called.group(1)] if called else []
    while todo:
        lines = comps.get(todo.pop(0), [])
        for l in lines:
            conv = _CONV.search(l)
            if conv:
                out, lhs, _, lhs_labels, _ = conv.groups()
                out = [int(d) for d in out.split(",") if d]
                lhs_line = next(d for d in lines if base.name_of(d) == lhs)
                summed = _dims(lhs_line)[lhs_labels.index("f")]
                return 2 * math.prod(out) * summed / 1e9
        todo += [c for l in lines for c in _CALLS.findall(l)]
    return None


def spaces(comps: dict, line: str) -> list:
    """The memory space of each operand, in order: "S(1)" ... or "hbm", as
    the called computation's `parameter(i)` lines give their layouts."""
    lines = comps.get(_CALLS.search(line).group(1), [])
    out = []
    for i in range(len(base.operands(line))):
        param = next((l for l in lines if f" parameter({i})" in l), "")
        space = _SPACE.search(param.split(" parameter(")[0])
        out.append(f"S({space.group(1)})" if space else "hbm")
    return out


def evicted(by_name: dict, operand: str) -> bool:
    """`operand` is a `copy-done` whose `copy-start` took a
    `collective-permute-done`: what arrived in fast memory, moved to HBM.
    `by_name`: the body's lines by the name each defines."""
    done = by_name.get(operand, "")
    if " copy-done(" not in done:
        return False
    start = by_name.get(base.operands(done)[0], "")
    return any(" collective-permute-done(" in by_name.get(o, "")
               for o in base.operands(start))


def products(comps: dict, body: list) -> list:
    """One row a matmul fusion of the body, in schedule order."""
    rows, by_name = [], {base.name_of(l): l for l in body}
    for at, line in enumerate(body):
        work = base.is_matmul(comps, line) and gflop(comps, line)
        if not work:
            continue
        t = base.ms(line)
        window = re.search(r'"output_window_bounds":\[([^\]]*)\]', line)
        rows.append({
            "name": base.name_of(line), "at": at, "shape": base.shape_of(line),
            "gflop": round(work, 3), "ms": round(t, 4),
            "peak_share": round(work / t / PEAK_TFLOPS, 4) if t else None,
            "window": window.group(1).replace('"', "").split(",") if window else [],
            "operands": [[o, s, evicted(by_name, o)] for o, s in
                         zip(base.operands(line), spaces(comps, line))]})
    return rows


def twins(rows: list) -> list:
    """Groups of products of equal GFLOP and output shape whose slowest
    estimate is more than `APART` over the fastest: [[name, ms], ...]."""
    groups = {}
    for r in rows:
        groups.setdefault((r["gflop"], r["shape"]), []).append(r)
    return [[[r["name"], r["ms"]] for r in sorted(g, key=lambda r: r["ms"])]
            for g in groups.values()
            if min(r["ms"] for r in g) > 0
            and max(r["ms"] for r in g) > (1 + APART) * min(r["ms"] for r in g)]


def excerpt(hlo: str) -> str:
    """The lines of `hlo` that `read` reads, as a text `read` reads the same."""
    comps = base.computations(hlo)
    bodies = base.scan_bodies(comps)
    heads = {m.group(1): l for l in hlo.splitlines()
             for m in [base._HEAD.match(l)] if m}

    def short(line: str) -> str:
        line = re.sub(r", metadata=\{[^}]*\}", "", line)
        config = re.search(r", backend_config=\{.*$", line)
        if not config:
            return line
        window = re.search(r'"output_window_bounds":\[[^\]]*\]', config.group(0))
        cycles = base._CYCLES.search(config.group(0))
        kept = ",".join(m.group(0) for m in (window, cycles) if m)
        return line[:config.start()] + (
            f', backend_config={{"window_config":{{{kept}}}}}' if kept else "")

    out, called = [], []
    for body in bodies.values():
        out.append(heads[next(n for n, lines in comps.items() if lines is body)])
        for l in body:
            matmul = base.is_matmul(comps, l)
            if matmul or re.search(r" (collective-permute|copy)-(start|done)\(", l):
                out.append(short(l))
            if matmul:
                called.append(_CALLS.search(l).group(1))
        out.append("}")
    while called:
        name = called.pop(0)
        lines = comps.get(name, [])
        conv = next((l for l in lines if _CONV.search(l)), "")
        wanted = set(base.operands(conv))
        out.append(heads[name])
        for l in lines:
            if (" parameter(" in l or l is conv or base.name_of(l) in wanted
                    or (not conv and _CALLS.search(l))):
                out.append(short(l))
        if not conv:
            called += [c for l in lines for c in _CALLS.findall(l)]
        out.append("}")
    return "\n".join(out) + "\n"


def read(hlo: str) -> dict:
    comps = base.computations(hlo)
    out = {}
    for side, body in base.scan_bodies(comps).items():
        rows = products(comps, body)
        out[side] = {"estimated_ms": round(sum(base.ms(l) for l in body), 3),
                     "matmul_ms": round(sum(r["ms"] for r in rows), 3),
                     "products": rows, "apart": twins(rows)}
    return out


def show(report: dict) -> None:
    for side, body in report.items():
        print(f"{side} body: XLA's estimate {body['estimated_ms']:.3f} ms a layer "
              f"({body['matmul_ms']:.3f} in matmul fusions)")
        for r in body["products"]:
            ops = " ".join(f"{o}:{'EVICTED' if ev else s}" for o, s, ev in r["operands"]
                           if s != "S(6)")
            print(f"  {r['at']:>4} {r['name']:<40}{r['shape']:<22}{r['gflop']:>8.2f} GFLOP "
                  f"{r['ms']:.3f} ms {100 * (r['peak_share'] or 0):5.1f}% "
                  f"window {'x'.join(r['window']):<8} {ops}")
        for group in body["apart"]:
            fast = group[0][1]
            print("  APART: " + ", ".join(
                f"{n} {t:.3f} ms (+{100 * (t / fast - 1):.0f}%)" for n, t in group))


if __name__ == "__main__":
    args = sys.argv[1:]
    if args and args[0] == "--compile":
        base.compile_step(args[1], args[2], int(args[3]) if len(args) > 3 else 22)
        args = [args[2]] + args[4:]
    with open(args[0]) as f:
        hlo = f.read()
    if "--excerpt" in args:
        with open(args[args.index("--excerpt") + 1], "w") as f:
            f.write(excerpt(hlo))
    report = read(hlo)
    if "--json" in args:
        print(json.dumps(report, indent=1))
    else:
        show(report)
