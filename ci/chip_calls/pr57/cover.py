"""PR 54's `cover.py` with a reading of each LINK, not of each permute alone.

XLA's scheduler prices a `collective-permute` by itself: it puts the start
far enough ahead of the done for THAT transfer. The link does not: every
permute over the same pairs shares it, so a small chunk started behind two
large ones arrives behind them. For each scan body and each of its links
(`fsdp`, `tp`), in schedule order, one line a done:

    MB ahead     the bytes started over the same pairs no later than this
                 done's own start and not yet taken when it stands (its own
                 chunk included): what a link that serves in the order of
                 the starts has to deliver before this done has its chunk
    since        XLA's `estimated_cycles` summed from the OLDEST such start
                 to this done, in ms
    GB/s needed  the one over the other: what the link has to carry for
                 this done to wait for nothing
    behind       the bytes started after its own start and not yet taken
    wait         what a link that serves its transfers in the order of
                 their starts at `--gbs` (35: what PR 54 read for 29.4 MB)
                 leaves this done waiting, the waits before it counted in

and each body's summed estimate and summed wait. `--tail` prints the
backward body's schedule from the first weight-gradient start on (the
`fsdp` permutes whose chunk has a leading 1: `[1, k, n]`), matmul fusions,
starts and dones alone. Needs no chip and no jax.

    python ci/chip_calls/pr57/cover.py <compiled.txt> [--gbs 35] [--tail] [--json]
    python ci/chip_calls/pr57/cover.py --compile <checkout> <out.txt> [layers]
"""

from __future__ import annotations

import itertools
import json
import math
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from ci.chip_calls.pr54 import cover as base  # noqa: E402

_BYTES = {"bf16": 2, "f32": 4, "f16": 2, "s32": 4, "s8": 1}


def megabytes(shape: str) -> float:
    """`bf16[1,2048,512]` -> 2.097152"""
    dtype, dims = re.match(r"(\w+)\[([\d,]*)\]", shape).groups()
    return _BYTES[dtype] * math.prod(int(d) for d in dims.split(",") if d) / 1e6


def is_dw(row: dict) -> bool:
    """A weight gradient's sent chunk: on `fsdp`, [1 (dp), k, n]."""
    return row["axis"] == "fsdp" and row["shard"].count(",") == 2


def link_queue(body: list, rows: list, gbs: float) -> list:
    """One row a done, in schedule order: see the module's docstring."""
    cost = [base.ms(line) for line in body]
    before = [0.0, *itertools.accumulate(cost)]   # the estimate up to an index
    free, finish, waited, out = {}, {}, 0.0, []
    events = sorted([(r["at"], 0, r) for r in rows] + [(r["done_at"], 1, r) for r in rows],
                    key=lambda e: e[:2])
    for at, is_done, r in events:
        now = before[at] + waited
        if not is_done:
            begin = max(now, free.get(r["axis"], 0.0))
            finish[r["start"]] = free[r["axis"]] = begin + megabytes(r["shard"]) / gbs
            continue
        pending = [p for p in rows if p["axis"] == r["axis"]
                   and p["at"] < at <= p["done_at"]]
        ahead = [p for p in pending if p["at"] <= r["at"]]
        since = sum(cost[min(p["at"] for p in ahead) + 1:at])
        mb = sum(megabytes(p["shard"]) for p in ahead)
        wait = max(0.0, finish[r["start"]] - now)
        waited += wait
        out.append({"done": r["done"], "axis": r["axis"], "shard": r["shard"],
                    "dw": is_dw(r), "at": at, "started_at": r["at"],
                    "ahead": [p["start"] for p in ahead],
                    "ahead_mb": round(mb, 1), "since_ms": round(since, 3),
                    "behind_mb": round(sum(megabytes(p["shard"]) for p in pending) - mb, 1),
                    "gbs_needed": round(mb / since, 1) if since else float("inf"),
                    "wait_ms": round(wait, 3)})
    return out


def dw_order(comps: dict, body: list, rows: list) -> list:
    """The weight-gradient dones, each with the dones of LATER starts that
    stand before the first matmul fusion that takes it: [] everywhere means
    the arrivals are taken in the order of their starts."""
    dws = sorted((r for r in rows if is_dw(r)), key=lambda r: r["at"])
    out = []
    for r in dws:
        taken = next((i for i, line in enumerate(body) if i > r["done_at"]
                      and r["done"] in base.operands(line)
                      and base.is_matmul(comps, line)), r["done_at"])
        out.append({"done": r["done"], "shard": r["shard"], "taken_at": taken,
                    "later_before": [p["done"] for p in dws
                                     if p["at"] > r["at"] and p["done_at"] < taken]})
    return out


def read(hlo: str, gbs: float = 35.0) -> dict:
    comps = base.computations(hlo)
    out = {}
    for side, body in base.scan_bodies(comps).items():
        rows = base.permutes(comps, body)
        queue = link_queue(body, rows, gbs)
        out[side] = {"instructions": len(body),
                     "estimated_ms": round(sum(base.ms(l) for l in body), 3),
                     "wait_ms": round(sum(q["wait_ms"] for q in queue), 3),
                     "queue": queue, "dw_order": dw_order(comps, body, rows)}
    return out


def tail(hlo: str) -> list:
    """The backward body from its first weight-gradient start on."""
    comps = base.computations(hlo)
    body = base.scan_bodies(comps)["backward"]
    rows = base.permutes(comps, body)
    first = min(r["at"] for r in rows if is_dw(r))
    out = []
    for i, line in enumerate(body[first:], first):
        name = base.name_of(line)
        if " collective-permute-" in line:
            out.append(f"{i:>4} {name:<34}{base.shape_of(line)} "
                       f"{' '.join(base.operands(line)[:1])}")
        elif base.is_matmul(comps, line) or "tpu_custom_call" in line:
            takes = [o for o in base.operands(line) if "collective-permute" in o]
            out.append(f"{i:>4}   {name:<32}{base.shape_of(line)} {base.ms(line):.3f} ms"
                       f"{' <- ' + ', '.join(takes) if takes else ''}")
    return out


def show(report: dict, gbs: float) -> None:
    for side, body in report.items():
        print(f"{side} body: {body['instructions']} instructions, XLA's estimate "
              f"{body['estimated_ms']:.3f} ms a layer; a link that serves in start "
              f"order at {gbs:g} GB/s leaves {body['wait_ms']:.3f} ms of waits")
        for q in body["queue"]:
            print(f"  {q['at']:>4} {q['done']:<28}{q['axis']:<5}{q['shard']:<20}"
                  f"{q['ahead_mb']:>6.1f} MB ahead ({len(q['ahead'])}) over "
                  f"{q['since_ms']:.3f} ms = {q['gbs_needed']:>6.1f} GB/s needed, "
                  f"{q['behind_mb']:>5.1f} behind, wait {q['wait_ms']:.3f}"
                  + ("  dw" if q["dw"] else ""))
        for d in body["dw_order"]:
            if d["later_before"]:
                print(f"  out of order: {', '.join(d['later_before'])} stand before "
                      f"the product that takes {d['done']} {d['shard']}")


if __name__ == "__main__":
    args = sys.argv[1:]
    if args and args[0] == "--compile":
        base.compile_step(args[1], args[2], int(args[3]) if len(args) > 3 else 22)
        args = [args[2]] + args[4:]
    gbs = float(args[args.index("--gbs") + 1]) if "--gbs" in args else 35.0
    with open(args[0]) as f:
        hlo = f.read()
    if "--tail" in args:
        print("\n".join(tail(hlo)))
    elif "--json" in args:
        print(json.dumps(read(hlo, gbs), indent=1))
    else:
        show(read(hlo, gbs), gbs)
