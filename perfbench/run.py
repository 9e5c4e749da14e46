"""perfbench: one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process every run; nothing outlives it but jax's compile cache. This
process never touches jax: the cell's driver asks ray_tpu for the worker or
replica that holds the chips. The last line of stdout is the result; its
last key, and the last lines of stderr, are the numbers compared, each
beside its limit.
Without the chips the cell asks for there is no result and the exit code is
not 0. `--cpu-rehearsal` runs the same control flow on the CPU (for tests,
at tiny sizes); it tags every line, never prints a result line, exits 10.
"""

from __future__ import annotations

T_START = __import__("time").time()

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from perfbench.lib.manifest import (Manifest, apply_overrides,  # noqa: E402
                                    prepare_env)


class _Ticker:
    """Sleeps 50 ms at a time and keeps the worst overshoot: a machine that
    stalls every process (a neighbour opening a chip does, for seconds) shows
    here, so that such a run can be told from a slow program."""

    def __init__(self):
        import threading

        self.worst_ms, self.over_100ms = 0.0, 0
        threading.Thread(target=self._loop, daemon=True).start()

    def _loop(self):
        while True:
            t = time.perf_counter()
            time.sleep(0.05)
            late = 1e3 * (time.perf_counter() - t - 0.05)
            self.worst_ms = max(self.worst_ms, late)
            self.over_100ms += late > 100.0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--root", default=ROOT,
                    help="where BENCHMARK.json is (default: this checkout)")
    ap.add_argument("--override", action="append", metavar="KEY=JSON",
                    help="lay a value over the traffic file (sweeps by hand)")
    ap.add_argument("--control", default=None,
                    help="run the correctness control in this lower precision "
                         "in the program's place (see perfbench/control.py)")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args()

    if not os.path.isdir(os.path.join(ROOT, "ray_tpu")):
        print("perfbench: no ray_tpu/ beside perfbench/: there is no system "
              "under test in this directory", file=sys.stderr)
        return 3
    tag = "[CPU REHEARSAL] " if args.cpu_rehearsal else ""
    man = Manifest(args.root)
    cell = man.cell(args.workload)
    config = man.load_config(cell["config"])
    traffic = apply_overrides(man.load_traffic(cell["traffic"]), args.override)

    prepare_env(ROOT, args.cpu_rehearsal)
    out_dir = os.path.join(ROOT, ".perfbench_out", args.workload)
    os.makedirs(out_dir, exist_ok=True)

    ticker = _Ticker()
    driver = man.load_module("drivers", traffic["driver"])
    ctx = {
        "cell": cell, "config": config, "traffic": traffic, "seed": args.seed,
        "seconds": args.seconds, "trace": bool(args.trace),
        "rehearsal": args.cpu_rehearsal, "out_dir": out_dir,
        "control": args.control, "t_start": T_START,
        "overridden": [o.partition("=")[0] for o in args.override or []],
        "reference_file": man.find("references", config["reference"] + ".py"),
    }
    rec = driver.run(ctx)
    # `serve_env`: the Serve settings a serving driver put in force, each
    # with the file it came from (`manifest.lay_serve_env`): what deployment
    # this row measured
    rec.update({"t_start": T_START, "config": config, "traffic": traffic,
                "cell": cell, "seconds": args.seconds, "traced": bool(args.trace),
                "serve_env": ctx.get("serve_env", {})})

    # what the metric readers read, kept beside the compile cache for whoever
    # wants to look closer (overwritten by the next run of this cell)
    with open(os.path.join(out_dir, "last_run.json"), "w") as f:
        json.dump({k: v for k, v in rec.items() if k != "rows"}, f, default=str)

    # every number that decides `correct`, each beside its limit: the
    # driver's comparisons, the requests that failed, the driver's own yes / no
    compared = list(rec["compared"]) + [
        ("failed", rec["failed"], 0),
        ("other_check_not_ok", int(not rec.get("correct_extra", True)), 0)]
    correct = True
    for name, value, limit in compared:
        ok = value <= limit
        correct = correct and ok
        print(f"{tag}[correct] {name} = {value:.6g}  limit {limit:g}  "
              f"{'ok' if ok else 'NOT OK'}", flush=True)

    dev = rec["device"]
    kinds = ["per_layer" if args.trace else "end_to_end"]
    if args.override:  # a sweep by hand reads all it can from its one run
        kinds = ["end_to_end", "per_layer"]
    metrics = {}
    for m in [m for k in kinds for m in man.metrics_for(cell["name"], k)]:
        value = man.load_module("metrics", m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": correct, "attempted": rec["attempted"],
            "failed": rec["failed"], "metrics": metrics,
            "device": {"platform": dev["platform"], "kind": dev["kind"],
                       "count": dev["count"],
                       "memory_peak_bytes": rec["memory_peak_bytes"]}}
    if args.trace and (rec.get("trace") or not args.cpu_rehearsal):
        tr = rec["trace"]
        line["device"].update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        line["breakdown"] = {"device_ops": tr["device_ops"],
                             "idle_gaps": tr["idle_gaps"]}
    if args.override or args.control:
        line["not_the_cell"] = {"override": args.override, "control": args.control}
    # the line's last key, and the last lines on standard error (what the
    # driver's record keeps of a run that is not correct)
    line["compared"] = {name: {"value": value, "limit": limit}
                        for name, value, limit in compared}
    print(f"{tag}[setup] worker asked for at {rec['t_ask'] - T_START:.2f}s, "
          f"first device at {rec['t_device'] - T_START:.2f}s, window open at "
          f"{rec['t_open'] - T_START:.2f}s; run took {time.time() - T_START:.1f}s; "
          f"the parent's 50 ms sleeps ran late by at most {ticker.worst_ms:.0f} ms "
          f"({ticker.over_100ms} by over 100 ms)", flush=True)
    if args.cpu_rehearsal:
        print(f"{tag}would report: {json.dumps(line)}", flush=True)
        print(f"{tag}rehearsal finished; this is not a chip result", flush=True)
        return 10
    if "jax" in sys.modules:
        print("perfbench: the parent imported jax", file=sys.stderr)
        return 4
    for name, c in line["compared"].items():
        print(f"[correct] {name} = {c['value']:.6g}  limit {c['limit']:g}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
