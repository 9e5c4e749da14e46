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


# What each source publishes (its `config.json`; for the five hybrid ones the
# catalog row of the model-configs guide, copied here so that the test needs
# no file outside the repo): every width, and every key some configuration
# of that source reduces, at its published value. A configuration may cut
# depth, held experts and a share of the vocabulary, never a width.
_MISTRAL = dict(hidden_size=4096, intermediate_size=14336, num_attention_heads=32,
                num_key_value_heads=8, vocab_size=32768, rope_theta=1e6,
                num_hidden_layers=32)
PUBLISHED = {
    "mistral-7b-v0.3.1chip": _MISTRAL,
    "mistral-7b-v0.3.4chip": _MISTRAL,
    "internlm2-1.8b": dict(
        hidden_size=2048, intermediate_size=8192, num_attention_heads=16,
        num_key_value_heads=8, vocab_size=92544, rope_theta=1e6,
        num_hidden_layers=24),
    "kimi-linear-48b-a3b.1of4": dict(
        hidden_size=2304, intermediate_size=9216, moe_intermediate_size=1024,
        num_attention_heads=32, num_key_value_heads=32, head_dim=72,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, num_experts_per_token=8, num_shared_experts=1,
        num_hidden_layers=27, num_experts=256, vocab_size=163840,
        linear_attn_config=dict(
            full_attn_layers=[4, 8, 12, 16, 20, 24, 27], head_dim=128,
            kda_layers=[1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18, 19,
                        21, 22, 23, 25, 26],
            num_heads=32, short_conv_kernel_size=4)),
    "jamba2-3b": dict(
        hidden_size=2560, intermediate_size=8192, num_attention_heads=20,
        num_key_value_heads=1, mamba_d_state=16, mamba_d_conv=4,
        mamba_dt_rank=160, mamba_expand=2, num_experts_per_tok=1,
        vocab_size=65536, num_hidden_layers=28),
    "openpangu-ultra-moe-718b.1of32": dict(
        hidden_size=7680, intermediate_size=18432, moe_intermediate_size=2048,
        num_attention_heads=128, num_key_value_heads=128, kv_lora_rank=512,
        q_lora_rank=1536, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, num_experts_per_tok=8, n_shared_experts=1,
        num_hidden_layers=61, first_k_dense_replace=3, n_routed_experts=256,
        vocab_size=153600),
    "evabyte-6.5b.1of4": dict(
        hidden_size=4096, intermediate_size=11008, num_attention_heads=32,
        num_key_value_heads=32, vocab_size=320, window_size=2048,
        chunk_size=16, num_pred_heads=8, num_hidden_layers=32),
    "granite-4.0-h-small.1of2": dict(
        hidden_size=4096, intermediate_size=768, shared_intermediate_size=1536,
        num_attention_heads=32, num_key_value_heads=8, mamba_n_heads=128,
        mamba_d_head=64, mamba_d_state=128, mamba_d_conv=4, mamba_expand=2,
        mamba_n_groups=1, num_experts_per_tok=10, num_hidden_layers=40,
        num_local_experts=72, vocab_size=100352),
}
# the contract's widths: a hidden, intermediate, latent, state or projection
# size, a head size, an expansion factor, the experts a token uses
WIDTH = re.compile(r"(_dim|_rank|_expand|_d_state|_d_head|_d_conv)$|hidden_size|"
                   r"intermediate_size|experts_per_tok|window_size|chunk_size")


def test_every_configuration_has_its_published_row(manifest):
    assert sorted(PUBLISHED) == sorted(c["name"] for c in manifest.data["configs"])


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_configurations_keep_the_published_widths(manifest, name):
    """A reduced key is no width, is smaller than published (a nested group:
    no width inside it changed) and the file says what was published under
    `source_<key>`; every other key of the table is as published."""
    entry, got, want = manifest.config_entry(name), manifest.load_config(name), PUBLISHED[name]
    assert set(entry["reduced"]) <= set(want), "a reduced key the table lacks"
    for key, value in want.items():
        if key not in entry["reduced"]:
            assert got[key] == value, (name, key)
            continue
        assert not WIDTH.search(key), (name, key)
        assert got["source_" + key] == value, (name, key)
        if isinstance(value, dict):
            assert got[key] != value
            for k, v in value.items():  # lists of layers are cut, numbers stay
                assert got[key][k] == v or (isinstance(v, list) and
                                            set(got[key][k]) < set(v)), (name, key, k)
        else:
            assert got[key] < value, (name, key)
