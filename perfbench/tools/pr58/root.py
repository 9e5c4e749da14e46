"""A benchmark root with PR 58's ten set-up metrics appended to `per_layer`,
for `run.py --root <dir>` (as a `benchmark` PR would append them: the
entries are `entries.json` beside this file; the readers are found in the
checkout's `perfbench/metrics/`). Entries that `BENCHMARK.json` has by then
are left as they are.

    python3 perfbench/tools/pr58/root.py _check/setup_root
    python3 perfbench/run.py --root _check/setup_root --workload <cell> --seed 7 --seconds 51 --trace 1
"""

import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(HERE))))

from perfbench.tools.probe import make_root  # noqa: E402


def main(out_dir: str) -> None:
    with tempfile.NamedTemporaryFile("w", suffix=".json") as probe:
        json.dump({"traffic": {}, "workloads": [], "metric_workloads": {}}, probe)
        probe.flush()
        bench = make_root(probe.name, out_dir)
    with open(os.path.join(HERE, "entries.json")) as f:
        entries = json.load(f)["per_layer"]
    have = {m["name"] for m in bench["per_layer"]}
    bench["per_layer"] += [m for m in entries if m["name"] not in have]
    with open(os.path.join(out_dir, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    print(f"root: {out_dir}/BENCHMARK.json with {len(bench['per_layer'])} per-layer metrics")


if __name__ == "__main__":
    main(sys.argv[1])
