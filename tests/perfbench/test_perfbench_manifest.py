"""BENCHMARK.json is well formed, every file it names is there, and every
configuration keeps the widths its source publishes.

A source's row is a file, `tests/perfbench/published/<configuration>.json`
(`source`: the URL, the configuration's own in BENCHMARK.json; `published`:
every width, and every key the configuration reduces, at its published
value: its `config.json`, for a model of the model-configs guide's catalog
the catalog row). This file holds the rules and names no configuration: a PR
that adds one adds its row's file, and edits nothing here.

Every check takes a `Manifest` (and the directory of rows beside it), so the
same checks judge a root that `perfbench/tools/probe.py` made, before its
entries reach BENCHMARK.json: `check_root`, and
`python3 perfbench/tools/probe.py check <root>`.
"""

import json
import os
import re
import sys
import traceback

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib.manifest import Manifest, load_py  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
probe = load_py(os.path.join(ROOT, "perfbench", "tools", "probe.py"))


def published_dir(root):
    return os.path.join(root, probe.PUBLISHED)


@pytest.fixture(scope="module")
def manifest():
    return Manifest(ROOT)


@pytest.fixture(scope="module")
def published():
    return published_dir(ROOT)


def test_top_level_keys_and_limits(manifest):
    b = manifest.data
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= b["run_seconds"] <= 51 and isinstance(b["run_seconds"], int)
    runs = 2 + 14 * 24  # a full check with the full 24 cells must fit
    assert runs * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(manifest.root, "BENCHMARK.json")) <= 64 * 1024
    for p in b["paths"]:
        assert os.path.isdir(os.path.join(manifest.root, p))
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
        assert os.path.isfile(os.path.join(manifest.root, c["file"]))
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




# the contract's widths: a hidden, intermediate, latent, state or projection
# size, a head size, an expansion factor, the experts a token uses
WIDTH = re.compile(r"(_dim|_rank|_expand|_d_state|_d_head|_d_conv)$|hidden_size|"
                   r"intermediate_size|experts_per_tok|window_size|chunk_size")
# keys of a configuration's file that are the benchmark's own, not the source's
BOOKKEEPING = {"reference", "deployment", "arithmetic", "assumed", "run",
               "experts_held"}
CONFIGS = [c["name"] for c in Manifest(ROOT).data["configs"]]


def row_path(published, name):
    return os.path.join(published, name + ".json")


def has_row(published, name):
    return os.path.isfile(row_path(published, name))


def no_row(published, name):
    return (f"configuration {name!r} has no published row: it belongs at "
            f"{row_path(published, name)}")


def load_row(published, name):
    assert has_row(published, name), no_row(published, name)
    with open(row_path(published, name)) as f:
        return json.load(f)


def test_every_configuration_has_its_published_row(manifest, published):
    """Every configuration its file, every file its configuration, and the
    file's `source` the one BENCHMARK.json gives that configuration."""
    names = [c["name"] for c in manifest.data["configs"]]
    lacking = [n for n in names if not has_row(published, n)]
    assert not lacking, "; ".join(no_row(published, n) for n in lacking)
    for name in names:
        row = load_row(published, name)
        assert set(row) == {"source", "published"}, (name, sorted(row))
        assert row["source"] == manifest.config_entry(name)["source"], name
    stray = sorted({os.path.splitext(f)[0] for f in os.listdir(published)} - set(names))
    assert not stray, (f"published rows of no configuration in BENCHMARK.json: "
                       f"{[row_path(published, s) for s in stray]}")


@pytest.mark.parametrize("name", CONFIGS)
def test_configurations_keep_the_published_widths(manifest, published, name):
    """A reduced key is no width, is smaller than published (a nested group:
    no width inside it changed) and the file says what was published under
    `source_<key>`; every other key of the row is as published."""
    entry, got = manifest.config_entry(name), manifest.load_config(name)
    want = load_row(published, name)["published"]
    assert set(entry["reduced"]) <= set(want), "a reduced key the row lacks"
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


@pytest.mark.parametrize("name", CONFIGS)
def test_a_published_row_leaves_no_width_out(manifest, published, name):
    """The converse: the check above walks the ROW's keys, so a width that the
    row omitted would never be compared. Every width among the top-level keys
    of the configuration's file is in its row."""
    want = load_row(published, name)["published"]
    widths = [k for k in manifest.load_config(name)
              if WIDTH.search(k) and not k.startswith("source_") and k not in BOOKKEEPING]
    assert widths and not set(widths) - set(want), (name, sorted(set(widths) - set(want)))


WHOLE = (test_top_level_keys_and_limits, test_names_units_and_sources,
         test_cells_configs_and_chips, test_every_cell_reports_what_the_contract_asks,
         test_every_metric_traffic_driver_and_reference_has_its_file)
A_CONFIGURATION = (test_configurations_keep_the_published_widths,
                   test_a_published_row_leaves_no_width_out)


def check_root(root):
    """Every check of this file on the benchmark root `root` (the checkout, or
    one `probe.py` made) and the rows under it: [(check, what it said)] of
    those that failed. A configuration without its row is the row check's to
    name; the checks of one configuration run on those that have theirs."""
    man, rows = Manifest(root), published_dir(root)
    calls = [(check, (man,)) for check in WHOLE]
    calls.append((test_every_configuration_has_its_published_row, (man, rows)))
    calls += [(check, (man, rows, c["name"])) for c in man.data["configs"]
              if has_row(rows, c["name"]) for check in A_CONFIGURATION]
    failed = []
    for check, args in calls:
        try:
            check(*args)
        except (Exception, SystemExit) as e:  # the boundary: report each, go on
            at = traceback.extract_tb(e.__traceback__)[-1]  # a bare assert says nothing
            failed.append((check.__name__ + "".join(f"[{a}]" for a in args[2:]),
                           f"{type(e).__name__}: {e}" if str(e) else
                           f"{type(e).__name__} at line {at.lineno}: {at.line}"))
    return failed


def a_further_configuration(man, published):
    """A probe (`perfbench/tools/probe.py`) of what a `model_config` PR adds to
    the benchmark, as files and entries alone: the manifest's LAST
    configuration (that has its row) once more under another name and source
    with its row under that name, its first cell's traffic file under another
    name, and a cell on both, listed under every metric that lists that first
    cell."""
    entry = next(c for c in reversed(man.data["configs"]) if has_row(published, c["name"]))
    cell = next(w for w in man.data["workloads"] if w["config"] == entry["name"])
    source = entry["source"] + "#brought"
    return {
        "configs": [{
            "entry": dict(entry, name="brought-config", source=source,
                          file="perfbench/configs/brought-config.json"),
            "file_body": man.load_config(entry["name"]),
            "published": dict(load_row(published, entry["name"]), source=source)}],
        "traffic": {"brought-traffic": man.load_traffic(cell["traffic"])},
        "workloads": [dict(cell, name="brought-cell", config="brought-config",
                           traffic="brought-traffic")],
        "metric_workloads": {
            m["name"]: ["brought-cell"]
            for m in man.data["end_to_end"] + man.data["per_layer"]
            if cell["name"] in m.get("workloads", [])}}, cell


@pytest.mark.parametrize("row_left_out", [False, True], ids=["with_its_row", "row_left_out"])
def test_a_ninth_configuration_is_files_and_entries(manifest, published, tmp_path,
                                                    row_left_out):
    """The promise of `perfbench/lib/manifest.py`, kept: one more
    configuration with its cell comes as files and appended entries, and
    every check of this file passes on the root that holds them. Without its
    row exactly the row check fails, and says which file is missing where."""
    spec, like = a_further_configuration(manifest, published)
    (tmp_path / "probe.json").write_text(json.dumps(spec))
    root = str(tmp_path / "root")
    probe.make_root(str(tmp_path / "probe.json"), root)
    made = Manifest(root)
    for kind in ("end_to_end", "per_layer"):
        assert [m["name"] for m in made.metrics_for("brought-cell", kind)] == \
            [m["name"] for m in manifest.metrics_for(like["name"], kind)]
    row = row_path(published_dir(root), "brought-config")
    if not row_left_out:
        assert check_root(root) == []
        return
    os.remove(row)
    (check, said), = check_root(root)
    assert check == "test_every_configuration_has_its_published_row"
    assert "'brought-config'" in said and row in said
