"""A cell tried before it is added: a benchmark root of its own, made from
the checkout's `BENCHMARK.json` with a probe file's entries APPENDED and its
traffic files written beside copies of the configurations, for
`run.py --root <dir>`; and one line of what such a run did to the replica's
queue. A probe file (`tools/probes/<cell>.json`) holds exactly what a later
PR adds as data: `traffic` (name -> the traffic file), `workloads` (the
cells' entries) and `metric_workloads` (metric -> the cells appended to its
`workloads` list). A probe runs on a configuration that is there, or BRINGS
one as a `model_config` PR would, under an optional `configs`: a list of
`{entry, file_body, published}` (the `BENCHMARK.json` entry, the content of
the configuration's file, the content of its published row's file,
`tests/perfbench/published/<name>.json`). `check` runs the manifest's tests
on such a root, before any of it reaches `BENCHMARK.json`.

    python3 perfbench/tools/probe.py root perfbench/tools/probes/<cell>.json _check/<dir>
    python3 perfbench/tools/probe.py check _check/<dir>
    python3 perfbench/run.py --root _check/<dir> --workload <cell> --seed 7 --seconds 51 --trace 0
    python3 perfbench/tools/probe.py read .perfbench_out/<cell>/last_run.json
"""

from __future__ import annotations

import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib.manifest import Manifest, load_py  # noqa: E402

PUBLISHED = os.path.join("tests", "perfbench", "published")


def make_root(probe_file: str, out_dir: str, root: str = ROOT) -> dict:
    """Write `<out_dir>/BENCHMARK.json` and the files it names that
    `Manifest` looks for under its own root (configurations, the probe's
    traffic) and the manifest's tests beside it (the published rows);
    everything else is found in this package. Returns the manifest
    written."""
    with open(probe_file) as f:
        probe = json.load(f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    copied = [c["file"] for c in bench["configs"]]
    brought = {}  # path under the root -> content
    for c in probe.get("configs", []):
        entry, file = c["entry"], c["entry"]["file"]
        if entry["name"] in {e["name"] for e in bench["configs"]} or file in copied:
            raise SystemExit(f"probe: configuration {entry['name']!r} or its file "
                             f"{file!r} is taken: a probe appends, it replaces none")
        if os.path.normpath(file) != file or not any(
                file.startswith(p + "/") for p in bench["paths"]):
            raise SystemExit(f"probe: configuration {entry['name']!r} keeps its file "
                             f"at {file!r}, which lies under none of {bench['paths']}")
        bench["configs"].append(entry)
        brought[file] = c["file_body"]
        brought[os.path.join(PUBLISHED, entry["name"] + ".json")] = c["published"]
    configs = {c["name"] for c in bench["configs"]}
    for cell in probe["workloads"]:
        if cell["config"] not in configs:
            raise SystemExit(f"probe: cell {cell['name']} wants configuration "
                             f"{cell['config']!r}, which BENCHMARK.json has not")
        bench["workloads"].append(cell)
    metrics = {m["name"]: m for m in bench["end_to_end"] + bench["per_layer"]}
    for name, cells in probe["metric_workloads"].items():
        if "workloads" not in metrics.get(name, {}):
            raise SystemExit(f"probe: no metric {name!r} with a `workloads` list")
        metrics[name]["workloads"] = metrics[name]["workloads"] + cells
    bench["paths"] = ["perfbench"]
    for file in copied:
        os.makedirs(os.path.dirname(os.path.join(out_dir, file)), exist_ok=True)
        shutil.copy(os.path.join(root, file), os.path.join(out_dir, file))
    shutil.copytree(os.path.join(root, PUBLISHED), os.path.join(out_dir, PUBLISHED),
                    dirs_exist_ok=True)
    for name, traffic in probe["traffic"].items():
        brought[os.path.join("perfbench", "traffic", name + ".json")] = traffic
    for file, content in brought.items():
        os.makedirs(os.path.dirname(os.path.join(out_dir, file)), exist_ok=True)
        with open(os.path.join(out_dir, file), "w") as f:
            json.dump(content, f, indent=2)
    with open(os.path.join(out_dir, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return bench


def read_run(last_run: str) -> dict:
    """What a run's record says of a replica driven past what it carries:
    delivered against offered tokens/s (the window's own requests' answer
    tokens / its seconds), mean busy slots and steps a second, the most of
    the window's requests in flight at once (sent, last token not yet in),
    their longest wait for a first token, and when the last answer came."""
    with open(last_run) as f:
        run = json.load(f)
    if "window_rows" not in run:
        raise SystemExit(f"probe: {last_run} is no serving run's record")
    man = Manifest(ROOT)
    read = lambda name: man.load_module("metrics", name).read(run)
    rows, seconds = run["window_rows"], run["seconds"]
    edges = sorted([(r["sent_s"], 1) for r in rows if "sent_s" in r] +
                   [(r["arrivals_s"][-1], -1) for r in rows if r["arrivals_s"]])
    most = flying = 0
    for _, step in edges:
        flying += step
        most = max(most, flying)
    t0 = run["t_open"]
    steps = [t for t, _, _ in run["replica"].get("steps", []) if t0 <= t < t0 + seconds]
    occupancy = read("engine.batch_occupancy")
    return {
        "cell": run["cell"]["name"], "correct_numbers": run["compared"],
        "attempted": run["attempted"], "failed": run["failed"],
        "serve_env": {k: v["value"] for k, v in run.get("serve_env", {}).items()},
        "offered_tokens_per_s": sum(r["max_new_tokens"] for r in rows) / seconds,
        "delivered_tokens_per_s": sum(
            1 for r in rows for t in r["arrivals_s"] if 0.0 <= t < seconds) / seconds,
        "busy_slots_mean": occupancy and occupancy / 100.0 * run["config"]["run"]["num_slots"],
        "steps_per_s": len(steps) / seconds,
        "decode_step_ms_p50": read("engine.decode_step_ms_p50"),
        "most_in_flight": most,
        "first_token_wait_s_max": max(
            (r["arrivals_s"][0] - r["due_s"] for r in rows if r["arrivals_s"]), default=None),
        "last_answer_after_close_s": run["drained_s"] - seconds,
        "setup_s": run["t_open"] - run["t_start"],
        "memory_peak_GB": run["memory_peak_bytes"] / 1e9,
    }


if __name__ == "__main__":
    if len(sys.argv) == 4 and sys.argv[1] == "root":
        made = make_root(sys.argv[2], sys.argv[3])
        print(f"probe: {sys.argv[3]}/BENCHMARK.json with "
              f"{len(made['workloads'])} cells")
    elif len(sys.argv) == 3 and sys.argv[1] == "read":
        print(json.dumps(read_run(sys.argv[2])))
    elif len(sys.argv) == 3 and sys.argv[1] == "check":
        failed = load_py(os.path.join(
            ROOT, "tests", "perfbench", "test_perfbench_manifest.py")).check_root(sys.argv[2])
        for check, said in failed:
            print(f"probe: FAILED {check}: {said}")
        if failed:
            raise SystemExit(1)
        print(f"probe: {sys.argv[2]} passes every check of test_perfbench_manifest.py")
    else:
        raise SystemExit(__doc__)
