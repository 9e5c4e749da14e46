"""PR 56's benchmark entries, ONE source for the probe file and for
`BENCHMARK.json`: the configuration `keye-vl-2.0-30b-a3b.1of8`, the cell
`keye-vl2-serve-docqa`, its ten per-layer metrics, and the lists that take
the cell's name.

    python3 perfbench/tools/pr56/entries.py probe    # -> perfbench/tools/probes/keye-vl2-serve-docqa.json
    python3 perfbench/tools/pr56/entries.py root _check/<dir>   # the probe root WITH the new metrics' entries
    python3 perfbench/tools/pr56/entries.py append   # the parent's BENCHMARK.json + the entries
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
CONFIG, CELL, TRAFFIC = "keye-vl-2.0-30b-a3b.1of8", "keye-vl2-serve-docqa", "docqa-open-loop"

KNEE = 0.8     # the sweep's (traffic file, `rate_why`)

CONFIG_ENTRY = {
    "name": CONFIG,
    "source": "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json",
    "file": f"perfbench/configs/{CONFIG}.json",
    "reduced": ["num_hidden_layers"],
    "why": "GQA 32:4 whose queries attend to the 2,048 rows a 16-head indexer scores highest, top-8 of 128 experts, no shared MLP, untied 152k head: 1 of 8 pipeline stages (6 of 48 layers), bf16, 8 slots x 32k"}
CELL_ENTRY = {
    "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
    "why": ("open loop RATE req/s = 0.65 x knee KNEE, prompts 8k-32k (median 14k), answers 64-384: "
            "sparse passes of 0.25-1.8 s between steps that score n keys, read 2,048 rows a "
            "slot; depth 6 of 48: host share x8")}
METRICS = [
    ("engine.dsa_step_ms_p50", "ms", "lower", "program_span", "engine"),
    ("engine.dsa_cache_bytes_per_step", "bytes", "lower", "program_counter", "engine"),
    ("dsa.selected_rows_share", "%", "lower", "program_counter", "engine"),
    ("engine.dsa_prefill_us_per_token", "us", "lower", "program_span", "engine"),
    ("moe.keye_experts_touched_share", "%", "lower", "program_counter", "expert layer"),
    ("kernels.dsa_moe_decode_hbm_share", "%", "higher", "device_trace", "kernels, decode"),
    ("kernels.dsa_scores_roofline", "%", "higher", "device_trace", "kernels, decode"),
    ("kernels.dsa_rows_roofline", "%", "higher", "device_trace", "kernels, decode"),
    ("kernels.dsa_select_roofline", "%", "higher", "device_trace", "kernels, prefill"),
    ("kernels.dsa_attention_roofline", "%", "higher", "device_trace", "kernels, prefill"),
]


def metric_entries():
    return [{"name": n, "unit": u, "better": b, "source": s, "layer": layer,
             "moves": "serve_tokens_per_s", "workloads": [CELL]}
            for n, u, b, s, layer in METRICS]


def joined_lists(bench):
    """The metrics whose `workloads` name every serving cell: the end-to-end
    `serve_tokens_per_s` and the token's way out (the twelve per-layer lists
    that hold all six serving cells)."""
    serving = {w["name"] for w in bench["workloads"]
               if "serve" in w["name"] and w["name"] != CELL}
    return [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if serving and serving <= set(m.get("workloads", []))]


def read(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def cell_entry():
    rate = read(f"perfbench/traffic/{TRAFFIC}.json")["rate_per_s"]
    why = CELL_ENTRY["why"].replace("RATE", f"{rate:g}").replace(
        "KNEE", f"{KNEE:g}")
    assert len(why) <= 200, len(why)
    return {**CELL_ENTRY, "why": why}


def main(what):
    bench = read("BENCHMARK.json")
    if what == "probe":
        bench["workloads"] = [w for w in bench["workloads"] if w["name"] != CELL]
        traffic = read(f"perfbench/traffic/{TRAFFIC}.json")
        probe = {"configs": [{"entry": CONFIG_ENTRY,
                              "file_body": read(CONFIG_ENTRY["file"]),
                              "published": read(f"tests/perfbench/published/{CONFIG}.json")}],
                 "traffic": {TRAFFIC: traffic}, "workloads": [cell_entry()],
                 "metric_workloads": {n: [CELL] for n in joined_lists(bench)},
                 "metrics": metric_entries()}
        out = os.path.join(ROOT, "perfbench", "tools", "probes", CELL + ".json")
        with open(out, "w") as f:
            json.dump(probe, f, indent=1)
        print(f"wrote {out}; lists joined: {sorted(probe['metric_workloads'])}")
    elif what == "append":
        # from the parent's file, so that a second call replaces the first's
        import subprocess
        bench = json.loads(subprocess.run(
            ["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT, check=True,
            capture_output=True, text=True).stdout)
        joined = joined_lists(bench)
        bench["configs"].append(CONFIG_ENTRY)
        bench["workloads"].append(cell_entry())
        for m in bench["end_to_end"] + bench["per_layer"]:
            if m["name"] in joined:
                m["workloads"].append(CELL)
        bench["per_layer"].extend(metric_entries())
        with open(os.path.join(ROOT, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f, indent=1)
            f.write("\n")
        print("appended")
    elif what == "root":
        sys.path.insert(0, ROOT)
        from perfbench.lib.manifest import load_py
        probe = load_py(os.path.join(ROOT, "perfbench", "tools", "probe.py"))
        made = probe.make_root(os.path.join(ROOT, "perfbench", "tools", "probes",
                                            CELL + ".json"), sys.argv[2])
        made["per_layer"].extend(metric_entries())
        with open(os.path.join(sys.argv[2], "BENCHMARK.json"), "w") as f:
            json.dump(made, f, indent=1)
        print(f"{sys.argv[2]}/BENCHMARK.json: {len(made['workloads'])} cells, "
              f"{len(made['per_layer'])} per-layer metrics")
    else:
        raise SystemExit(__doc__)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "")
