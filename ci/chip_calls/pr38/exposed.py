"""Exposed collective time of a kept trace, told two ways.

`by text` is the benchmark's rule (`perfbench/lib/xplane.py`): an operation
is a collective if its instruction's whole TEXT names one, operands
included, so a product that takes a `%collective-permute-done` operand
counts. `by opcode` looks at the instruction's own opcode, its name and the
computation it calls (`all-reduce(`, `%async-collective-start.8 = ... fusion(`,
`calls=%all-reduce-scatter`), never at its operands. Needs jax, no chip.

    python ci/chip_calls/pr38/exposed.py <file.xplane.pb> [top]
"""

from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from perfbench.lib import xplane  # noqa: E402

_OWN = re.compile(
    r"^%?(?P<name>[\w.\-]+)\s*=\s.*?[\]\}\)]\s(?P<op>[a-z][a-z0-9\-]*)\(")
_CALLEE = re.compile(r"calls=%([\w.\-]+)")


def collective_by_opcode(text: str) -> bool:
    m = _OWN.match(text)
    if not m:
        return bool(xplane._COLLECTIVE.search(text.split("(")[0]))
    callee = _CALLEE.search(text)
    return bool(xplane._COLLECTIVE.search(m["op"])
                or xplane._COLLECTIVE.search(m["name"])
                or m["name"].startswith("async-collective")
                or (callee and xplane._COLLECTIVE.search(callee.group(1))))


def reduce(planes: dict, top: int = 14) -> dict:
    """Per chip means over the traced window: busy and exposed collective
    seconds by both rules, and the operations by self time with their kind."""
    devices = {k: v for k, v in planes.items()
               if k.startswith("/device:") and xplane.OPS_LINE in v}
    if not devices:
        raise ValueError(f"no device plane in the trace: {sorted(planes)}")
    t0 = min(s for v in devices.values() for _, s, _ in v[xplane.OPS_LINE])
    t1 = max(s + d for v in devices.values() for _, s, d in v[xplane.OPS_LINE])
    out = {"devices": len(devices), "window_s": (t1 - t0) / 1e9}
    ops, busy = {}, 0.0
    exposed = {"text": 0.0, "opcode": 0.0}
    rules = {"text": lambda n: bool(xplane._COLLECTIVE.search(n)),
             "opcode": collective_by_opcode}
    for plane in devices.values():
        rows = xplane.self_times(plane[xplane.OPS_LINE])
        busy += xplane._total(xplane.union([(s, e) for _, s, e, _ in rows]))
        leaves = [(n, s, e) for n, s, e, own in rows if own >= 0.999 * (e - s)]
        for n, _, _, own in rows:
            key = (xplane.op_label(n), collective_by_opcode(n))
            ops[key] = ops.get(key, 0.0) + own
        for rule, is_coll in rules.items():
            coll = xplane.union([(s, e) for n, s, e in leaves if is_coll(n)])
            comp = xplane.union([(s, e) for n, s, e in leaves if not is_coll(n)])
            exposed[rule] += xplane._subtract(coll, comp)
    n = len(devices)
    out["busy_s"] = busy / n / 1e9
    for rule, v in exposed.items():
        out[f"exposed_s_by_{rule}"] = v / n / 1e9
        out[f"exposed_share_pct_by_{rule}"] = 100 * v / n / (t1 - t0)
    out["collective_self_s_by_opcode"] = sum(
        v for (_, c), v in ops.items() if c) / n / 1e9
    out["ops"] = [[k[0], "collective" if k[1] else "compute", v / n / 1e9]
                  for k, v in sorted(ops.items(), key=lambda kv: -kv[1])[:top]]
    return out


if __name__ == "__main__":
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 14
    print(json.dumps(reduce(xplane.load(sys.argv[1]), top), indent=1))
