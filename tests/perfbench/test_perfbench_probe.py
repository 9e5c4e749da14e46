"""`perfbench/tools/probe.py`: a benchmark root of its own for a cell that
is not added yet, and the one line it reads of such a run's record; and the
probe file PR 52 leaves for the PR after it (`internlm2-serve-saturated`):
data alone on an accepted configuration, its router queue stated by the
traffic file and wide enough for every request the run sends."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perfbench.lib import traffic as traffic_mod  # noqa: E402
from perfbench.lib.manifest import Manifest, lay_serve_env, load_py  # noqa: E402

probe = load_py(os.path.join(ROOT, "perfbench", "tools", "probe.py"))
PROBE_FILE = os.path.join(ROOT, "perfbench", "tools", "probes",
                          "internlm2-serve-saturated.json")
CELL = "internlm2-serve-saturated"
QUEUE = "RAY_TPU_SERVE_MAX_QUEUE_PER_REPLICA"


@pytest.fixture(scope="module")
def made(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("probe_root"))
    probe.make_root(PROBE_FILE, out)
    return Manifest(out)


def test_the_probe_root_appends_entries_and_adds_no_configuration(made):
    real = Manifest(ROOT)
    assert made.data["configs"] == real.data["configs"]
    assert made.data["workloads"][:-1] == real.data["workloads"]
    cell = made.cell(CELL)
    assert cell["config"] == "internlm2-1.8b" and cell["chips"] == 1
    assert len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert made.load_config(cell["config"]) == real.load_config(cell["config"])
    for kind in ("end_to_end", "per_layer"):  # nothing but appended names
        for was, now in zip(real.data[kind], made.data[kind]):
            lists = now.get("workloads", [])
            assert lists[:len(was.get("workloads", []))] == was.get("workloads", [])
            assert set(lists[len(was.get("workloads", [])):]) <= {CELL}
            assert {k: v for k, v in now.items() if k != "workloads"} == \
                {k: v for k, v in was.items() if k != "workloads"}


def test_the_probe_cell_reports_capacity_and_the_twelve_token_path_metrics(made):
    e2e = {m["name"] for m in made.metrics_for(CELL, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per = {m["name"] for m in made.metrics_for(CELL, "per_layer")}
    token_path = load_py(os.path.join(ROOT, "tests", "perfbench",
                                      "test_perfbench_token_path.py"))
    assert set(token_path.NAMES) <= per
    assert not {"kernels.decode_hbm_share", "serve.proxy_ms_p50"} & per
    for m in made.metrics_for(CELL, "per_layer"):
        assert m["moves"] in e2e


def test_the_probes_queue_holds_every_request_the_run_sends(made, monkeypatch, capsys):
    """The traffic file states the router's queue over the configuration's
    128; the window of 51 s and its warm traffic send fewer requests than it
    holds, so the router sheds none whatever the program delivers."""
    cell = made.cell(CELL)
    traffic, config = made.load_traffic(cell["traffic"]), made.load_config(cell["config"])
    assert traffic["driver"] == "open_loop_http" and traffic["rate_per_s"] == 34
    chat = Manifest(ROOT).load_traffic("chat-open-loop")
    for key in ("prompt_tokens", "answer_tokens", "arrival_cv", "warm_s", "limits",
                "request_timeout_s", "check_answers", "control"):
        assert traffic[key] == chat[key], key
    monkeypatch.delenv(QUEUE, raising=False)
    in_force = lay_serve_env({"cell": cell, "config": config, "traffic": traffic})
    assert in_force == {QUEUE: {"value": "2048",
                                "from": "traffic chat-saturated-open-loop"}}
    assert config["run"]["serve_env"] == {QUEUE: "128"}
    seconds = made.data["run_seconds"]
    schedule = traffic_mod.open_loop(traffic, 2**31 + 52, seconds, config["vocab_size"])
    assert len([r for r in schedule if r["due_s"] >= 0]) == 34 * seconds
    assert 34 * seconds < len(schedule) < int(in_force[QUEUE]["value"])
    assert not traffic_mod.slot_rule(traffic, config["run"]["num_slots"])["ok"]
    offered = sum(r["max_new_tokens"] for r in schedule if r["due_s"] >= 0) / seconds
    assert offered == pytest.approx(3467, abs=5)


@pytest.mark.parametrize("fault,said", [
    ({"workloads": [{"name": "x", "config": "nope", "traffic": "t", "chips": 1,
                     "why": "w"}]}, "which BENCHMARK.json has not"),
    ({"metric_workloads": {"setup_s": ["x"]}}, "with a `workloads` list"),
])
def test_a_probe_that_needs_more_than_data_is_refused(tmp_path, fault, said):
    spec = dict({"traffic": {}, "workloads": [], "metric_workloads": {}}, **fault)
    (tmp_path / "p.json").write_text(json.dumps(spec))
    with pytest.raises(SystemExit, match=said):
        probe.make_root(str(tmp_path / "p.json"), str(tmp_path / "out"))


def test_reading_a_saturated_runs_record(tmp_path):
    """Three requests of a 10 s window, by hand: two in flight at once, the
    last answer 4 s after the close, 10 of 14 offered tokens inside it."""
    row = lambda i, due, sent, arrivals, n: {
        "i": i, "due_s": due, "sent_s": sent, "arrivals_s": arrivals,
        "max_new_tokens": n, "ok": True}
    run = {
        "cell": {"name": "c"}, "seconds": 10.0, "t_open": 100.0, "t_start": 60.0,
        "compared": [["wrong_length_answers", 0.0, 0.0]], "attempted": 3, "failed": 0,
        "serve_env": {QUEUE: {"value": "2048", "from": "traffic t"}},
        "config": {"run": {"num_slots": 4}}, "drained_s": 14.0,
        "memory_peak_bytes": 13.5e9,
        "window_rows": [row(0, 0.5, 0.5, [1.0, 2.0, 3.0, 4.0], 4),
                        row(1, 2.0, 2.1, [5.0, 6.0, 7.0, 8.0, 9.0, 9.5], 6),
                        row(2, 9.0, 9.0, [12.0, 13.0, 13.5, 14.0], 4)],
        "replica": {"steps": [[99.0, 4, 0]] + [[100.0 + t, 3, 0] for t in range(10)],
                    "spans": {"bench.engine_step": [[101.0, 101.006, 0]],
                              "bench.prefill": []}}}
    path = tmp_path / "last_run.json"
    path.write_text(json.dumps(run))
    got = probe.read_run(str(path))
    assert got["offered_tokens_per_s"] == 1.4 and got["delivered_tokens_per_s"] == 1.0
    assert got["most_in_flight"] == 2 and got["last_answer_after_close_s"] == 4.0
    assert got["busy_slots_mean"] == pytest.approx(3.0) and got["steps_per_s"] == 1.0
    assert got["first_token_wait_s_max"] == 3.0 and got["setup_s"] == 40.0
    assert got["decode_step_ms_p50"] == pytest.approx(6.0)
    assert got["serve_env"] == {QUEUE: "2048"} and got["memory_peak_GB"] == 13.5


@pytest.fixture(scope="module")
def bringing():
    """A probe that brings a configuration (`test_perfbench_manifest.py` builds
    it from the manifest's last one), and the cell it copies."""
    rows = load_py(os.path.join(ROOT, "tests", "perfbench", "test_perfbench_manifest.py"))
    return rows.a_further_configuration(Manifest(ROOT), rows.published_dir(ROOT))


def test_a_probe_root_loads_the_configuration_it_brings(tmp_path, bringing):
    spec, like = bringing
    (tmp_path / "p.json").write_text(json.dumps(spec))
    probe.make_root(str(tmp_path / "p.json"), str(tmp_path / "out"))
    made, real = Manifest(str(tmp_path / "out")), Manifest(ROOT)
    brought = spec["configs"][0]
    assert made.data["configs"] == real.data["configs"] + [brought["entry"]]
    assert made.load_config("brought-config") == brought["file_body"] == \
        real.load_config(like["config"])
    rows = tmp_path / "out" / "tests" / "perfbench" / "published"
    assert sorted(os.listdir(rows)) == sorted(
        os.listdir(os.path.join(ROOT, probe.PUBLISHED)) + ["brought-config.json"])
    assert json.loads((rows / "brought-config.json").read_text()) == brought["published"]
    assert made.cell("brought-cell")["config"] == "brought-config"
    assert made.load_traffic("brought-traffic") == real.load_traffic(like["traffic"])


@pytest.mark.parametrize("change,said", [
    (lambda real: {"name": real["name"]}, "is taken"),
    (lambda real: {"file": real["file"]}, "is taken"),
    (lambda real: {"file": "perfbench/../brought.json"}, "lies under none of"),
    (lambda real: {"file": "ray_tpu/brought.json"}, "lies under none of"),
], ids=["name_taken", "file_taken", "file_leads_out", "file_outside_paths"])
def test_a_brought_configuration_that_replaces_or_strays_is_refused(
        tmp_path, bringing, change, said):
    spec = json.loads(json.dumps(bringing[0]))
    spec["configs"][0]["entry"].update(change(Manifest(ROOT).data["configs"][0]))
    (tmp_path / "p.json").write_text(json.dumps(spec))
    with pytest.raises(SystemExit, match=said):
        probe.make_root(str(tmp_path / "p.json"), str(tmp_path / "out"))
    assert not (tmp_path / "out").exists()
