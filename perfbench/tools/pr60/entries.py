"""PR 60's benchmark entries, ONE source for the probe file and for
`BENCHMARK.json`: the configuration `command-a-plus-05-2026.1of8`, the cell
`command-a-plus-serve-mixedqueue`, its nine per-layer metrics, and the lists
that take the cell's name. Every new entry goes at the END of its list, as
the benchmark's contract asks; a cell's name joins an existing list at its end.

    python3 perfbench/tools/pr60/entries.py probe    # -> perfbench/tools/probes/command-a-plus-serve-mixedqueue.json
    python3 perfbench/tools/pr60/entries.py append   # the parent's BENCHMARK.json + the entries
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
CONFIG, CELL = "command-a-plus-05-2026.1of8", "command-a-plus-serve-mixedqueue"
TRAFFIC = "mixed-queue-open-loop"

KNEE = 1.7     # by 51 s windows (traffic file, `rate_why`; the 30 s sweep had said 1.8)

CONFIG_ENTRY = {
    "name": CONFIG,
    "source": "https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json",
    "file": f"perfbench/configs/{CONFIG}.json",
    "reduced": ["num_hidden_layers", "layer_types", "num_experts", "vocab_size"],
    "why": "window 4,096 and full attention 3:1, GQA 128:8, one LayerNorm a layer, attention + top-8 of 128 experts + mean of 4 shared in parallel: 1 of 8 chips of 1 of 8 stages (4 layers, 16 experts), bf16"}
CELL_ENTRY = {
    "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
    "why": ("open loop RATE req/s = 0.65 x knee KNEE; 65% prompts 256-3k + 35% 8k-48k, ONE queue: banded "
            "passes between steps on ring + full rows; window ends in its longest gap: reads OFFERED; "
            "experts 1/8, attn 8x")}
METRICS = [
    ("engine.swa_step_ms_p50", "ms", "lower", "program_span", "engine"),
    ("engine.swa_prefill_us_per_token", "us", "lower", "program_span", "engine"),
    ("engine.swa_cache_bytes_per_step", "bytes", "lower", "program_counter", "engine"),
    ("swa.window_rows_share", "%", "lower", "program_counter", "engine"),
    ("moe.cmda_experts_touched_share", "%", "lower", "program_counter", "expert layer"),
    ("kernels.swa_prefill_roofline", "%", "higher", "device_trace", "kernels, prefill"),
    ("kernels.swa_decode_roofline", "%", "higher", "device_trace", "kernels, decode"),
    ("kernels.swa_moe_decode_roofline", "%", "higher", "device_trace", "kernels, decode"),
    ("serve.swa_window_mfu", "%", "higher", "device_trace", "device"),
]


def metric_entries():
    return [{"name": n, "unit": u, "better": b, "source": s, "layer": layer,
             "moves": "serve_tokens_per_s", "workloads": [CELL]}
            for n, u, b, s, layer in METRICS]


def joined_lists(bench):
    """The metrics whose `workloads` name every serving cell: the end-to-end
    `serve_tokens_per_s` and the token's way out (the twelve per-layer lists
    that hold every cell that reports it)."""
    serving = {w["name"] for w in bench["workloads"]
               if "serve" in w["name"] and w["name"] not in (CELL, "internlm2-serve-chat")}
    return [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if serving and serving <= set(m.get("workloads", []))]


def read(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def cell_entry():
    rate = read(f"perfbench/traffic/{TRAFFIC}.json")["rate_per_s"]
    why = CELL_ENTRY["why"].replace("RATE", f"{rate:g}").replace("KNEE", f"{KNEE:g}")
    assert len(why) <= 200, len(why)
    return {**CELL_ENTRY, "why": why}


def parent():
    return json.loads(subprocess.run(
        ["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT, check=True,
        capture_output=True, text=True).stdout)


def main(what):
    bench = parent()   # so that a second call replaces the first's
    if what == "probe":
        probe = {"configs": [{"entry": CONFIG_ENTRY,
                              "file_body": read(CONFIG_ENTRY["file"]),
                              "published": read(f"tests/perfbench/published/{CONFIG}.json")}],
                 "traffic": {TRAFFIC: read(f"perfbench/traffic/{TRAFFIC}.json")},
                 "workloads": [cell_entry()],
                 "metric_workloads": {n: [CELL] for n in joined_lists(bench)},
                 "metrics": metric_entries()}
        out = os.path.join(ROOT, "perfbench", "tools", "probes", CELL + ".json")
        with open(out, "w") as f:
            json.dump(probe, f, indent=1)
        print(f"wrote {out}; lists joined: {sorted(probe['metric_workloads'])}")
    elif what == "append":
        joined = joined_lists(bench)
        bench["configs"].append(CONFIG_ENTRY)
        bench["workloads"].append(cell_entry())
        for m in bench["end_to_end"] + bench["per_layer"]:
            if m["name"] in joined:
                m["workloads"].append(CELL)
        bench["per_layer"] += metric_entries()
        with open(os.path.join(ROOT, "BENCHMARK.json"), "w") as f:
            json.dump(bench, f, indent=1)
            f.write("\n")
        print(f"appended; lists joined: {len(joined)}")
    else:
        raise SystemExit(__doc__)


if __name__ == "__main__":
    main(sys.argv[1] if len(sys.argv) > 1 else "")
