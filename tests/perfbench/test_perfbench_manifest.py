"""BENCHMARK.json is well formed and every file it names is there."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    import sys
    sys.path.insert(0, ROOT)
    from perfbench.lib.manifest import Manifest

    return Manifest(ROOT)


def test_top_level_keys_and_limits(manifest):
    b = manifest.data
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    runs = 2 + 14 * 24  # a full check with the full 24 cells must fit
    assert runs * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    for p in b["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert all(not w.startswith("/") and ".." not in w for w in b["command"])


def test_names_units_and_sources(manifest):
    b = manifest.data
    metrics = b["end_to_end"] + b["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in b["end_to_end"])
    for c in b["configs"]:
        assert NAME.match(c["name"]) and len(c["reduced"]) <= 16
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200


def test_cells_configs_and_chips(manifest):
    b = manifest.data
    cells = b["workloads"]
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert {w["config"] for w in cells} == {c["name"] for c in b["configs"]}
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    files = [c["file"] for c in b["configs"]]
    assert len(files) == len(set(files))


def test_every_cell_reports_what_the_contract_asks(manifest):
    b = manifest.data
    e2e_names = {m["name"] for m in b["end_to_end"]}
    for w in b["workloads"]:
        e2e = {m["name"] for m in manifest.metrics_for(w["name"], "end_to_end")}
        per = manifest.metrics_for(w["name"], "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2 and per
        for m in per:  # a per-layer metric moves something this cell reports
            assert m["moves"] in e2e, (w["name"], m["name"])
    for m in b["per_layer"]:
        assert m["moves"] in e2e_names
        for cell in m.get("workloads", []):
            assert manifest.cell(cell)


def test_every_metric_traffic_driver_and_reference_has_its_file(manifest):
    b = manifest.data
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(manifest.load_module("metrics", m["name"]).read)
    for w in b["workloads"]:
        traffic = manifest.load_traffic(w["traffic"])
        assert callable(manifest.load_module("drivers", traffic["driver"]).run)
        config = manifest.load_config(w["config"])
        assert manifest.find("references", config["reference"] + ".py")


def test_configurations_keep_the_published_widths(manifest):
    published = {
        "mistral-7b-v0.3": dict(hidden_size=4096, intermediate_size=14336,
                                num_attention_heads=32, num_key_value_heads=8,
                                vocab_size=32768, rope_theta=1e6, num_hidden_layers=32),
        "internlm2-1.8b": dict(hidden_size=2048, intermediate_size=8192,
                               num_attention_heads=16, num_key_value_heads=8,
                               vocab_size=92544, rope_theta=1e6, num_hidden_layers=24),
    }
    for c in manifest.data["configs"]:
        want = next(v for k, v in published.items() if c["name"].startswith(k))
        got = manifest.load_config(c["name"])
        for key, value in want.items():
            if key in c["reduced"]:
                assert got[key] < value and got["source_" + key] == value
            else:
                assert got[key] == value, (c["name"], key)
        assert not any(k.endswith(("_dim", "_rank", "_size")) for k in c["reduced"])
