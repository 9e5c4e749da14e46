"""The head of the four-chip training step in a compiled text: what stands
where in the ENTRY computation's schedule.

In schedule order: every collective (start and done of a permute with the
bytes it moves and the pairs, an all-gather, all-reduce or reduce-scatter of
any spelling), every matmul fusion (GFLOP, XLA's `estimated_cycles` in ms at
the v5e's 1.5 GHz, its output) and the two `while`s of the layers' scan. For
each permute: the matmul ms XLA estimates between its start and its done, and
the rate its bytes would need to arrive inside them. Needs no chip and no jax.

    python ci/chip_calls/pr61/head.py <compiled.txt> [--json]
    python ci/chip_calls/pr61/head.py --compile <checkout> <out.txt> [layers]
"""

from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from ci.chip_calls.pr54 import cover as base  # noqa: E402
from ci.chip_calls.pr59 import twins  # noqa: E402

_BYTES = {"bf16": 2, "f32": 4, "s32": 4, "u32": 4, "pred": 1, "f16": 2, "s8": 1}
_COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|all-to-all|"
                         r"collective-permute|collective-broadcast")


def entry(hlo: str) -> list:
    """The ENTRY computation's lines, in schedule order."""
    lines, inside = [], False
    for line in hlo.splitlines():
        if line.startswith("ENTRY "):
            inside = True
        elif inside and line.startswith("}"):
            break
        elif inside:
            lines.append(line)
    return lines


def mbytes(line: str) -> float:
    m = re.search(r"= \(?(\w+)\[([\d,]*)\]", line)
    n = 1
    for d in m.group(2).split(","):
        n *= int(d) if d else 1
    return n * _BYTES.get(m.group(1), 4) / 1e6


def read(hlo: str) -> dict:
    comps = base.computations(hlo)
    body = entry(hlo)
    rows = []
    for i, line in enumerate(body):
        name = base.name_of(line) if re.match(r"\s*(?:ROOT )?%", line) else None
        if name is None:
            continue
        op = re.search(r"[\]\})] ([a-z][a-z0-9\-]*)\(", line)
        op = op.group(1) if op else ""
        callee = re.search(r"calls=%([\w.\-]+)", line)
        if op == "while":
            rows.append({"at": i, "name": name, "kind": "while"})
        elif _COLLECTIVE.search(op) or _COLLECTIVE.search(name) or (
                callee and _COLLECTIVE.search(callee.group(1))):
            pairs = re.search(r"source_target_pairs=(\{[\d,\{\}]*\})", line)
            rows.append({"at": i, "name": name, "kind": op, "ms": base.ms(line),
                         "shape": base.shape_of(line), "mb": mbytes(line),
                         "pairs": pairs.group(1) if pairs else "",
                         "operands": base.operands(line)})
        elif base.is_matmul(comps, line):
            rows.append({"at": i, "name": name, "kind": "matmul",
                         "ms": base.ms(line), "gflop": twins.gflop(comps, line),
                         "shape": base.shape_of(line),
                         "operands": base.operands(line)})
    whiles = [r["at"] for r in rows if r["kind"] == "while"]
    for r in rows:
        if r["kind"] != "collective-permute-start":
            continue
        done = next(d for d in rows if d["kind"] == "collective-permute-done"
                    and r["name"] in d["operands"])
        between = [m for m in rows if r["at"] < m["at"] < done["at"]]
        r["done_at"] = done["at"]
        r["matmul_ms_between"] = sum(m["ms"] for m in between if m["kind"] == "matmul")
        r["all_ms_between"] = sum(base.ms(l) for l in body[r["at"] + 1:done["at"]])
        r["spans_a_while"] = any(r["at"] < w < done["at"] for w in whiles)
        r["taken_by"] = next((m["name"] for m in rows if m["at"] > done["at"]
                              and done["name"] in m.get("operands", ())), None)
    return {"lines": len(body), "whiles": whiles, "rows": rows}


def show(report: dict) -> None:
    print(f"# ENTRY: {report['lines']} instructions, whiles at {report['whiles']}")
    for r in report["rows"]:
        if r["kind"] == "while":
            print(f"{r['at']:5d} WHILE {r['name']}")
        elif r["kind"] == "matmul":
            print(f"{r['at']:5d}   matmul {r['name']:44s} {r['gflop'] or 0:7.1f} GFLOP "
                  f"{r['ms']:6.3f} ms  {r['shape']}  <- {','.join(r['operands'][:4])}")
        else:
            extra = ""
            if "done_at" in r:
                extra = (f" done at {r['done_at']}, matmul {r['matmul_ms_between']:.3f} ms "
                         f"(all {r['all_ms_between']:.3f}) between"
                         f"{', SPANS A WHILE' if r['spans_a_while'] else ''}"
                         f", taken by {r['taken_by']}")
            print(f"{r['at']:5d} {r['kind']:26s} {r['name']:34s} {r['shape']} "
                  f"{r['mb']:7.1f} MB {r['ms']:6.3f} ms {r['pairs']}{extra}"
                  f"  <- {','.join(r['operands'][:3])}")


if __name__ == "__main__":
    args = sys.argv[1:]
    if args and args[0] == "--compile":
        base.compile_step(args[1], args[2], int(args[3]) if len(args) > 3 else 22)
        args = [args[2]] + args[4:]
    with open(args[0]) as f:
        report = read(f.read())
    print(json.dumps(report, indent=1)) if "--json" in args else show(report)
