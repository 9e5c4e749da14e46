"""PR 63's benchmark entries, ONE source for the probe file and for
`BENCHMARK.json` (after `tools/pr60/entries.py`): the configuration
`smallthinker-21b-a3b.12of52`, the cell `smallthinker-serve-longanswer`, its
eight per-layer metrics, and the lists that take the cell's name (every
serving cell's, and the two of Command A+'s readers that read this cell's
spans as they stand). Every new
entry goes at the END of its list, as the benchmark's contract asks; a cell's
name joins an existing list at its end.

    python3 perfbench/tools/pr63/entries.py probe    # -> perfbench/tools/probes/smallthinker-serve-longanswer.json
    python3 perfbench/tools/pr63/entries.py append   # the parent's BENCHMARK.json + the entries
"""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
CONFIG, CELL = "smallthinker-21b-a3b.12of52", "smallthinker-serve-longanswer"
TRAFFIC = "context-longanswer-open-loop"

KNEE = 0.9     # by four 51 s windows (traffic file, `rate_why`)

CONFIG_ENTRY = {
    "name": CONFIG,
    "source": "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json",
    "file": f"perfbench/configs/{CONFIG}.json",
    "reduced": ["num_hidden_layers", "rope_layout", "sliding_window_layout"],
    "why": "global (no positions) and window-4,096 attention 1:3, GQA 28:4, sequential block, router reads the attention's input, top-6 of 64 ReGLU experts all held: a 12-layer stage of 52, whole vocabulary, bf16"}
CELL_ENTRY = {
    "name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1,
    "why": ("open loop RATE req/s = SHARE x knee KNEE; prompts 512-8k, answers 256-1.5k: BUSY of 16 slots busy, "
            "TOUCHED% of 768 held experts a step, ring wraps in 45%; depth 12 of 52: host share of a token ~4x deployment's")}
METRICS = [
    ("engine.sthink_step_ms_p50", "ms", "lower", "program_span", "engine"),
    ("engine.sthink_prefill_us_per_token", "us", "lower", "program_span", "engine"),
    ("engine.sthink_cache_bytes_per_step", "bytes", "lower", "program_counter", "engine"),
    ("swa.sthink_wrapped_slots_share", "%", "lower", "program_counter", "engine"),
    ("moe.sthink_experts_touched_share", "%", "lower", "program_counter", "expert layer"),
    ("kernels.sthink_moe_decode_roofline", "%", "higher", "device_trace", "kernels, decode"),
    ("kernels.sthink_prefill_roofline", "%", "higher", "device_trace", "kernels, prefill"),
    ("serve.sthink_window_mfu", "%", "higher", "device_trace", "device"),
]
# Command A+'s readers of the window form's spans that read no key of a
# configuration file (`engine.step` with `window_rows` and `full_rows`,
# `engine.prefill_dispatch` with `tokens`): they read this cell's spans as
# they stand, so the cell joins their lists and the step and the pass of BOTH
# blocks are read under one name. (The other seven read Cohere's key names
# through `lib.cmda_counts`: `layer_types`, `sliding_window`,
# `intermediate_size`; PERF.md section 7 item 13 queues their merging.)
SHARED_READERS = ("engine.swa_step_ms_p50", "engine.swa_prefill_us_per_token")
# what the cell's `why` says of the chip's readings (PERF.md section 5, PR 63)
SHARE, BUSY, TOUCHED = "0.65", "3", "24"


def metric_entries():
    return [{"name": n, "unit": u, "better": b, "source": s, "layer": layer,
             "moves": "serve_tokens_per_s", "workloads": [CELL]}
            for n, u, b, s, layer in METRICS]


def joined_lists(bench):
    """The metrics whose `workloads` name every serving cell: the end-to-end
    `serve_tokens_per_s` and the token's way out (the twelve per-layer lists
    that hold every cell that reports it), and `SHARED_READERS`."""
    serving = {w["name"] for w in bench["workloads"]
               if "serve" in w["name"] and w["name"] not in (CELL, "internlm2-serve-chat")}
    return [m["name"] for m in bench["end_to_end"] + bench["per_layer"]
            if serving and serving <= set(m.get("workloads", []))
            or m["name"] in SHARED_READERS]


def read(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def cell_entry():
    rate = read(f"perfbench/traffic/{TRAFFIC}.json")["rate_per_s"]
    why = CELL_ENTRY["why"]
    for name, value in (("RATE", f"{rate:g}"), ("KNEE", f"{KNEE:g}"), ("SHARE", SHARE),
                        ("BUSY", BUSY), ("TOUCHED", TOUCHED)):
        why = why.replace(name, value)
    return {**CELL_ENTRY, "why": why}


def parent():
    return json.loads(subprocess.run(
        ["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT, check=True,
        capture_output=True, text=True).stdout)


def main(what):
    bench = parent()   # so that a second call replaces the first's
    for e in [CONFIG_ENTRY, cell_entry()]:
        for key in ("why", "source"):
            assert key not in e or (1 <= len(e[key]) <= 200 and e[key].isascii()
                                    and e[key].isprintable()), (e["name"], key)
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
