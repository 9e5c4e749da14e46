"""The SmallThinker cell: its toy runs through the real command on the CPU
from a throw-away root (untraced, traced, under the `int8` control, which
must come out as not correct); the manifest's new entries, held by NAME and
never by their place in a list; every string of the new entries ASCII,
printable and 1 to 200 characters long (what refused PR 62); the
configuration file against the catalog row it was drawn from, published
widths both ways; the file's `arithmetic` recomputed from its own keys; the
counts functions against hand sums; the traffic file's lengths and slot rule;
every new reader on a synthetic span list and trace, None where there is
nothing to read; which answers the check compares.

The model-configs guide's "shares add up to the whole" test is
`tests/test_smallthinker.py::test_the_shares_of_64_experts_add_up_to_the_uncut_layer`."""

import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "perfbench", "run.py")
sys.path.insert(0, ROOT)

from perfbench.lib import sthink_counts as sc  # noqa: E402
from perfbench.lib import traffic as traffic_mod  # noqa: E402
from perfbench.lib.manifest import Manifest, load_py  # noqa: E402

CELL, CONFIG = "smallthinker-serve-longanswer", "smallthinker-21b-a3b.12of52"
TRAFFIC_NAME = "context-longanswer-open-loop"
SOURCE = "https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/blob/main/config.json"
NEW_METRICS = {"engine.sthink_step_ms_p50", "engine.sthink_prefill_us_per_token",
               "engine.sthink_cache_bytes_per_step", "swa.sthink_wrapped_slots_share",
               "moe.sthink_experts_touched_share", "kernels.sthink_moe_decode_roofline",
               "kernels.sthink_prefill_roofline", "serve.sthink_window_mfu"}
# Command A+'s readers that read no key of a configuration file: the cell is
# on their lists, so step and pass of both blocks are read under one name
SHARED_READERS = {"engine.swa_step_ms_p50", "engine.swa_prefill_us_per_token"}
DEVICE_METRICS = {m for m in NEW_METRICS if m.startswith(("kernels.", "serve."))}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
LAYOUT = [0, 1, 1, 1]
# the catalog row's `config` (model-configs guide, architectures.jsonl,
# SmallThinker-21BA3B-Instruct), copied here so that the test needs no file
# outside the repo
ROW = {"head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
       "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
       "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
       "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
       "num_attention_heads": 28, "num_hidden_layers": 52, "num_key_value_heads": 4,
       "rms_norm_eps": 1e-06, "rope_layout": LAYOUT * 13, "rope_scaling": None,
       "rope_theta": 1500000, "sliding_window_layout": LAYOUT * 13,
       "sliding_window_size": 4096, "tie_word_embeddings": False, "vocab_size": 151936}
REDUCED = {"num_hidden_layers": 12, "rope_layout": LAYOUT * 3,
           "sliding_window_layout": LAYOUT * 3}
TOY = {**ROW, "head_dim": 16, "hidden_size": 64, "moe_ffn_hidden_size": 32,
       "num_attention_heads": 14, "num_key_value_heads": 2,
       "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 3,
       "experts_held": {"of": 8, "first": 0, "count": 8}, "num_hidden_layers": 8,
       "rope_layout": LAYOUT * 2, "sliding_window_layout": LAYOUT * 2,
       "sliding_window_size": 16, "vocab_size": 96, "torch_dtype": "float32",
       "reference": "smallthinker",
       "run": {"num_slots": 4, "max_len": 128, "max_concurrent_queries": 32}}
TRAFFIC = {"kind": "open_loop", "driver": "open_loop_http_sthink",
           "rate_per_s": 4.0, "arrival_cv": 1.0, "warm_s": 1,
           "prompt_tokens": {"log_mean": 2.9, "log_sd": 0.8, "min": 4, "max": 90},
           "answer_tokens": {"log_mean": 2.8, "log_sd": 0.4, "min": 8, "max": 30},
           "slot_rule": {"token_gap_ms": 20, "ttft_ms": 30}, "request_timeout_s": 60,
           "warm": {"prefill_buckets": [16, 128], "admission_batches": [1],
                    "attention_buckets": [64, 128]},
           "trace_window_s": [0.0, 2.0], "check_answers": 1000,
           "check_decode_steps": 24, "control": "int8",
           "limits": {"token_gap_mean_spacings": 0.01,
                      "prefill_logits_rel_err": 1e-4, "route_margin_max": 1e-4}}


def _throw_away_root(tmp_path):
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    extra = tmp_path / "extra"
    for sub in ("configs", "traffic"):
        (extra / sub).mkdir(parents=True)
    (extra / "configs" / "toy-sthink.json").write_text(json.dumps(TOY))
    (extra / "traffic" / "toy-longanswer.json").write_text(json.dumps(TRAFFIC))
    metrics = {"end_to_end": [], "per_layer": []}
    for kind in metrics:
        for m in real[kind]:
            m = dict(m)
            if "workloads" in m:
                if CELL not in m["workloads"]:
                    continue
                m["workloads"] = ["toy-sthink-serve"]
            metrics[kind].append(m)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": real["command"], "paths": ["extra"], "run_seconds": 2,
        "configs": [{"name": "toy-sthink", "source": "none",
                     "file": "extra/configs/toy-sthink.json", "reduced": [],
                     "why": "throw-away"}],
        "workloads": [{"name": "toy-sthink-serve", "config": "toy-sthink",
                       "traffic": "toy-longanswer", "chips": 1, "why": "throw-away"}],
        **metrics}))
    return str(tmp_path)


def _run(args, timeout=500):
    return subprocess.run([sys.executable, RUN] + args, capture_output=True,
                          text=True, timeout=timeout, cwd=ROOT,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))


def _would_report(stdout):
    line = next(l for l in stdout.splitlines() if "would report: " in l)
    return json.loads(line.split("would report: ", 1)[1])


@pytest.mark.parametrize("trace,control,expects", [
    (0, None, {"serve_tokens_per_s", "setup_s"}),
    (1, None, (NEW_METRICS - DEVICE_METRICS) | SHARED_READERS | {
        "engine.batch_occupancy", "compile.s", "engine.wakes_per_token"}),
    (0, "int8", set()),
    (0, "late_route", set()),
])
def test_the_sthink_toy_runs_through_the_real_command(tmp_path, trace, control, expects):
    """Untraced: the end-to-end metrics; traced: every new metric that reads
    the program's spans reads a number (the two kernels' shares and the
    window's read the device's trace, which the CPU has none of, and are left
    out); the `int8` control comes out as not correct BY THE LOGITS, and so
    does a fault of the block planted in the program that serves
    (`late_route`: the router reads the FFN's input). At least
    one of the compared answers has wrapped its ring. Exit 10."""
    args = ["--root", _throw_away_root(tmp_path), "--workload", "toy-sthink-serve",
            "--seed", str(2**31 + 11), "--seconds", "2", "--trace", str(trace),
            "--cpu-rehearsal"] + (["--control", control] if control else [])
    p = _run(args)
    assert p.returncode == 10, p.stdout[-3000:] + p.stderr[-3000:]
    rep = _would_report(p.stdout)
    assert rep["failed"] == 0 and rep["attempted"] > 0
    assert rep["correct"] is (control is None), p.stdout[-3000:]
    assert expects <= set(rep["metrics"]), rep["metrics"]
    assert not DEVICE_METRICS & set(rep["metrics"])
    assert set(rep["compared"]) >= {"prefill_logits_rel_err", "route_margin_max",
                                    "token_gap_mean_spacings"}
    answers = [json.loads(l.split("answer: ", 1)[1].replace("'", '"'))
               for l in p.stdout.splitlines() if "[correct] answer: " in l]
    assert len(answers) == 2
    assert max(a["prompt_len"] + a["answer_len"] for a in answers) > 16 + 8
    wrapped = re.search(r"\[check\] (\d) of 2 compared answers wrapped", p.stdout)
    assert wrapped and int(wrapped.group(1)) >= 1
    if trace:
        m = {k: v["value"] for k, v in rep["metrics"].items()}
        assert m["engine.sthink_step_ms_p50"] > 0
        assert m["engine.sthink_prefill_us_per_token"] > 0
        # Command A+'s readers of the same spans read the same numbers
        assert m["engine.swa_step_ms_p50"] == m["engine.sthink_step_ms_p50"]
        assert m["engine.swa_prefill_us_per_token"] == \
            m["engine.sthink_prefill_us_per_token"]
        # at most 4 busy slots of 120 positions, 2 x 2 x 16 x 4 B a row
        assert 0 < m["engine.sthink_cache_bytes_per_step"] <= 4 * (2 * 120 + 6 * 16) * 256
        assert 0 < m["swa.sthink_wrapped_slots_share"] <= 100
        assert 0 < m["moe.sthink_experts_touched_share"] <= 100
    if control:
        failing = [l for l in p.stdout.splitlines() if "NOT OK" in l]
        assert any("prefill_logits_rel_err" in l for l in failing), failing


def test_without_a_chip_the_new_cell_gives_no_result():
    p = _run(["--workload", CELL, "--seed", "1", "--seconds", "6", "--trace", "0"])
    assert p.returncode not in (0, 10), p.stdout[-2000:]
    assert "needs a TPU" in p.stdout + p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_the_driver_refuses_a_program_without_the_block(tmp_path, monkeypatch):
    """On the parent's checkout (a `hybrid.py` that knows no `route_from`) the
    driver says so and exits before any process of the cluster exists."""
    driver = load_py(os.path.join(ROOT, "perfbench", "drivers", "open_loop_http_sthink.py"))
    (tmp_path / "ray_tpu" / "models").mkdir(parents=True)
    (tmp_path / "ray_tpu" / "models" / "hybrid.py").write_text("class SwaCache: pass\n")
    monkeypatch.setattr(driver, "ROOT", str(tmp_path))
    with pytest.raises(SystemExit, match="has no route_from"):
        driver.run({})


# ---- the manifest's new entries, by name -----------------------------------

def _strings(entry):
    return [(k, v) for k, v in entry.items() if isinstance(v, str)]


def test_the_manifest_takes_the_new_entries():
    """Held by NAME, never by place (a later PR appends behind them)."""
    man = Manifest(ROOT)
    cell = man.cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == TRAFFIC_NAME
    entry = man.config_entry(CONFIG)
    assert entry["reduced"] == list(REDUCED) and entry["source"] == SOURCE
    assert entry["file"] == f"perfbench/configs/{CONFIG}.json"
    new = [m for m in man.data["per_layer"] if m["name"] in NEW_METRICS]
    assert {m["name"] for m in new} == NEW_METRICS and len(new) == len(NEW_METRICS)
    for m in new:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        assert m["unit"] in ("ms", "us", "bytes", "%")
        assert set(m) == {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        man.find("metrics", m["name"] + ".py")
    layers = {m["layer"] for m in man.data["per_layer"] if m["name"] not in NEW_METRICS}
    assert {m["layer"] for m in new} <= layers      # no layer of its own spelling
    e2e = {m["name"] for m in man.metrics_for(CELL, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert NEW_METRICS | SHARED_READERS | {
        "engine.batch_occupancy", "device.peak_hbm_bytes.serve",
        "engine.driver_device_wait_share", "engine.wakes_per_token"} <= per_layer
    # (these read Cohere's key names through `lib.cmda_counts`)
    assert not {"kernels.decode_hbm_share", "swa.window_rows_share",
                "moe.cmda_experts_touched_share", "serve.swa_window_mfu"} & per_layer
    # every list that Command A+'s cell joined took this cell's name too, and
    # so did the two lists of its own whose readers read this cell's spans
    joined = [m["name"] for m in man.data["end_to_end"] + man.data["per_layer"]
              if "command-a-plus-serve-mixedqueue" in m.get("workloads", [])
              and len(m["workloads"]) > 1]
    assert len(joined) == 15 and SHARED_READERS <= set(joined)
    for m in man.data["end_to_end"] + man.data["per_layer"]:
        if m["name"] in joined:
            assert CELL in m["workloads"], m["name"]


def test_every_string_of_the_new_entries_is_what_the_driver_admits():
    """What refused PR 62: a configuration's and a cell's `why` and `source`,
    a metric's `layer`, every `name`, 1 to 200 ASCII printable characters on
    one line; every name at most 64 of letters, digits, `_`, `.`, `-`."""
    man = Manifest(ROOT)
    entries = [man.cell(CELL), man.config_entry(CONFIG)] + [
        m for m in man.data["per_layer"] if m["name"] in NEW_METRICS]
    assert len(entries) == 10
    for e in entries:
        for key, value in _strings(e):
            assert 1 <= len(value) <= 200 and value.isascii() and value.isprintable() \
                and "\t" not in value and "\n" not in value, (e["name"], key, len(value))
        assert NAME.match(e["name"]), e["name"]
    cell, entry = entries[:2]
    assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
    assert all(NAME.match(k) for k in entry["reduced"]) and len(entry["reduced"]) <= 16
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert set(entry) == {"name", "source", "file", "reduced", "why"}
    assert re.fullmatch(r"[A-Za-z0-9_.\-/]+", entry["file"])
    tr = man.load_traffic(TRAFFIC_NAME)
    assert f"{tr['rate_per_s']:g} req/s" in cell["why"] and "12 of 52" in cell["why"]


def test_the_traffic_file_is_the_issues_traffic():
    man = Manifest(ROOT)
    tr, conf = man.load_traffic(TRAFFIC_NAME), man.load_config(CONFIG)
    assert tr["kind"] == "open_loop" and tr["arrival_cv"] == 1.0
    # ISSUE 63's 20 s of warm traffic: at the cell's rate the longest answer
    # lasts ~15 s, so every answer that streams into the window has its twin
    assert tr["warm_s"] == 20
    assert "mix" not in tr and "order_seed" not in tr     # ONE kind, the seed's order
    assert tr["driver"] == "open_loop_http_sthink"
    assert tr["prompt_tokens"] == {"log_mean": 8.03, "log_sd": 0.6, "min": 512,
                                   "max": 8192}
    assert tr["answer_tokens"] == {"log_mean": 6.46, "log_sd": 0.5, "min": 256,
                                   "max": 1536}
    assert tr["prompt_tokens"]["max"] + tr["answer_tokens"]["max"] == 9728 \
        < conf["run"]["max_len"] - 2
    assert set(tr["limits"]) == {"token_gap_mean_spacings", "prefill_logits_rel_err",
                                 "route_margin_max"}
    assert set(tr["limits_why"]) == set(tr["limits"])
    # int8 is the lower precision; the other two are faults of the block
    # planted in the serving program (`lib.sthink_replica.FAULTS`)
    from perfbench.lib import sthink_replica
    assert tr["control"] == "int8" \
        and tr["controls"] == ["int8"] + sorted(sthink_replica.FAULTS)
    assert tr["warm"]["prefill_buckets"] == [512, 1024, 2048, 4096, 16384]
    assert tr["warm"]["attention_buckets"] == [conf["run"]["max_len"]]
    # the trace opens before the window does: the period's first request is
    # due at 0.0 under every seed, so a prompt pass lies whole inside it
    a, b = tr["trace_window_s"]
    assert a < 0 < b and b - a == 4 and -a < tr["warm_s"]
    # the length draws: medians, and where a window's contexts stand to 4,096
    big = 2**31 + 11
    a, a2, b = (traffic_mod.open_loop(tr, s, 51, 151936) for s in (big, big, 7))
    assert a == a2 and a != b
    assert min(r["due_s"] for r in a if r["due_s"] >= 0) == 0.0 == \
        min(r["due_s"] for r in b if r["due_s"] >= 0)
    window = [r for r in a if 0 <= r["due_s"] < 51]
    assert len(window) == round(tr["rate_per_s"] * 51)
    prompts = sorted(len(r["prompt"]) for r in window)
    answers = sorted(r["max_new_tokens"] for r in window)
    assert 2600 <= prompts[len(prompts) // 2] <= 3500
    assert 560 <= answers[len(answers) // 2] <= 720
    assert prompts[0] >= 512 and prompts[-1] <= 8192
    assert answers[0] >= 256 and answers[-1] <= 1536
    assert max(t for r in a[:3] for t in r["prompt"]) > 32768    # the whole vocabulary
    n = len(window)
    ends_past = sum(len(r["prompt"]) + r["max_new_tokens"] > 4096 for r in window)
    begins_past = sum(len(r["prompt"]) > 4096 for r in window)
    assert 0.3 <= ends_past / n <= 0.6 and 0.2 <= begins_past / n <= 0.45
    assert ends_past > begins_past                    # some cross it in their answer


def test_the_slot_rule_holds_at_the_cells_rate():
    man = Manifest(ROOT)
    tr, conf = man.load_traffic(TRAFFIC_NAME), man.load_config(CONFIG)
    rule = traffic_mod.slot_rule(tr, conf["run"]["num_slots"])
    assert rule["ok"] and tr["rate_per_s"] <= rule["max_rate_per_s"]
    assert not traffic_mod.slot_rule(dict(tr, rate_per_s=4 * rule["max_rate_per_s"]),
                                     conf["run"]["num_slots"])["ok"]


# ---- the configuration file -------------------------------------------------

@pytest.fixture(scope="module")
def c():
    return Manifest(ROOT).load_config(CONFIG)


def test_the_configuration_file_is_the_row_but_for_the_cut(c):
    """Every key of the catalog row is as published but the three reduced
    keys (the depth and the two layouts cut with it), each of which carries
    the published value beside it; published widths both ways."""
    for key, value in ROW.items():
        if key in REDUCED:
            assert c[key] == REDUCED[key] and c["source_" + key] == value, key
        else:
            assert c[key] == value, key
    assert set(c) - set(ROW) == {
        "torch_dtype", "reference", "experts_held", "reduced", "deployment",
        "arithmetic", "assumed", "run"} | {"source_" + k for k in REDUCED}
    assert set(c["reduced"]) == set(REDUCED)
    assert c["experts_held"] == {"of": 64, "first": 0, "count": 64}     # every expert
    assert {"route_input", "reglu", "rotary", "window", "qk_norm", "secondary_experts",
            "dtypes"} <= {k for k, v in c["assumed"].items() if "first to check" in v}
    assert c["run"]["num_slots"] == 16 and c["run"]["max_len"] == 16384 \
        == c["max_position_embeddings"]
    assert c["run"]["max_len"] % c["sliding_window_size"] == 0
    assert c["run"]["control"] == "int8" and c["reference"] == "smallthinker"
    assert "12 layers" in c["deployment"] and "four times" in c["deployment"]
    pub = json.load(open(os.path.join(ROOT, "tests", "perfbench", "published",
                                      CONFIG + ".json")))
    assert pub == {"source": SOURCE, "published": ROW}


def test_the_arithmetic_is_recomputed_from_the_files_own_keys(c):
    a = c["arithmetic"]
    d, H, kvh, hd = 2560, 28, 4, 128
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"]) == (d, H, kvh, hd)
    assert a["attention_params"] == 2 * d * H * hd + 2 * d * kvh * hd == 20971520 \
        == sc.attn_params(c)
    assert a["router_params"] == d * 64 == 163840 == sc.router_params(c)
    assert a["expert_params"] == 3 * d * c["moe_ffn_hidden_size"] == 5898240 \
        == sc.expert_params(c)
    assert a["experts_params_a_layer"] == 64 * a["expert_params"] == 377487360
    assert a["norm_params_a_layer"] == 2 * d
    assert a["layer_params"] == sc.layer_params(c) == 398627840 == \
        a["attention_params"] + a["router_params"] + a["experts_params_a_layer"] + 2 * d
    assert a["layers_params"] == 12 * a["layer_params"]
    assert a["embedding_and_head_params"] == 2 * c["vocab_size"] * d == 777912320
    assert a["params"] == sc.param_count(c) == \
        a["layers_params"] + a["embedding_and_head_params"] + a["final_norm_params"]
    assert a["weight_bytes"] == 2 * a["params"]
    assert a["weight_bytes"] / 1e9 == pytest.approx(11.12, abs=0.005)
    assert a["row_bytes_a_position_and_layer"] == sc.row_bytes(c) == 2048
    run = c["run"]
    assert a["slot_rows_at_max_len"] == sc.slot_rows(c, run["max_len"]) \
        == 3 * 16384 + 9 * 4096 == 86016
    assert a["slot_bytes"] == 86016 * 2048
    assert a["cache_bytes"] == sc.cache_bytes(c, run["num_slots"], run["max_len"]) \
        == 16 * a["slot_bytes"]
    assert a["weights_and_cache_bytes"] == a["weight_bytes"] + a["cache_bytes"]
    assert a["weights_and_cache_bytes"] / 1e9 == pytest.approx(13.94, abs=0.005)
    assert a["share_of_16_GB_chip"] == round(a["weights_and_cache_bytes"] / 16e9, 2)


def test_the_file_makes_the_program_configuration(c):
    from perfbench.lib import sthink_model

    cfg = sthink_model.model_config(c)
    assert cfg.runs() == (("full", 1), ("swa", 3)) * 3       # SIX runs, global first
    assert cfg.run_ffns() == ("moe",) * 6
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rope_theta,
            cfg.swa_window) == (28, 4, 128, 1.5e6, 4096)
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k, cfg.d_expert, cfg.n_shared) \
        == (64, tuple(range(64)), 6, 768, 0)
    assert (cfg.swa_block, cfg.swa_norm, cfg.swa_rotary, cfg.route_from, cfg.gate_act,
            cfg.router) == ("sequential", "rms", "half", "mixer", "relu", "softmax")
    assert cfg.vocab_size == 151936 and cfg.windowed and cfg.untied_head
    assert cfg.norm_eps == 1e-6
    with pytest.raises(ValueError, match="SmallThinker stack"):
        sthink_model.model_config({**c, "tie_word_embeddings": True})
    with pytest.raises(ValueError, match="SmallThinker stack"):
        sthink_model.model_config({**c, "rope_layout": [1] * 12})


def test_the_program_holds_what_the_arithmetic_says(c):
    """The program's own parameter tree and cache, as shapes: a window
    layer's cache is 4,096 rows a slot, not `max_len`."""
    import jax

    from perfbench.lib import sthink_model
    from ray_tpu.models import hybrid

    cfg = sthink_model.model_config(c)
    params = jax.eval_shape(lambda k: hybrid.init_params(k, cfg), jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) == sc.param_count(c)
    state = jax.eval_shape(lambda: cfg.make_cache(16, 16384).state)
    assert state["wk"].shape == (9, 16, 4, 4096, 128)
    assert state["k"].shape == (3, 16, 4, 16384, 128)
    assert sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(state)) \
        == sc.cache_bytes(c, 16, 16384)


def test_counts_against_hand_sums(c):
    assert sc.layer_kinds(c) == (9, 3) and sc.held_expert_slots(c) == 768
    # a slot at 6,000 positions: a window layer reads 4,096 rows, a global one all
    assert sc.rows_per_step(c, 4096, 6000) == 9 * 4096 + 3 * 6000
    assert sc.cache_bytes_per_step(c, 4096, 6000) == (9 * 4096 + 3 * 6000) * 2048
    fixed = 2 * (12 * (20971520 + 163840) + 151936 * 2560)
    assert sc.decode_fixed_weight_bytes(c) == fixed == sc.decode_step_bytes(c, 0, 0, 0)
    assert sc.decode_step_bytes(c, 0, 0, 10) - fixed == 10 * 2 * 5898240
    assert sc.attended_pairs(c, 1000) == (1000 * 1001 / 2, 1000 * 1001 / 2)
    assert sc.attended_pairs(c, 6000) == (4096 * 4097 / 2 + 1904 * 4096, 6000 * 6001 / 2)
    assert sc.attention_flops(c, 6000) == 4 * 28 * 128 * (
        9 * (4096 * 4097 / 2 + 1904 * 4096) + 3 * 6000 * 6001 / 2)
    # every expert held: all six assignments a layer land
    per_layer = 20971520 + 163840
    assert sc.product_flops(c, 1) == 2 * (12 * per_layer + 12 * 6 * 5898240)
    assert sc.product_flops(c, 10, 7, head_rows=10) == 2 * (
        10 * 12 * per_layer + 7 * 5898240 + 10 * 151936 * 2560)
    assert sc.pass_kernel_calls(c, {"tokens": 6000, "bucket": 16384}) == 12 * 2
    assert sc.pass_kernel_calls(c, {"tokens": 3000, "bucket": 4096}) == 12


def test_choose_samples_takes_one_crossing_and_one_past_the_window():
    from perfbench.lib import sthink_replica

    s = lambda n, a: {"prompt": [1] * n, "answer": [2] * a}
    lens = lambda got: [(len(x["prompt"]), len(x["answer"])) for x in got]
    got = sthink_replica.choose_samples(
        [s(9000, 300), s(300, 400), s(3900, 600), s(3000, 1500), s(4100, 256),
         s(5000, 700), s(3950, 100)], 4096, 512)
    # 3,950 + 100 never crosses; 3,000 crosses at step 1,096, behind the replay
    assert lens(got) == [(3900, 600), (4100, 256)]
    got = sthink_replica.choose_samples([s(300, 400), s(700, 300), s(5000, 700)], 4096, 512)
    assert lens(got) == [(5000, 700)] + [(700, 300)]          # none crosses: the median
    got = sthink_replica.choose_samples([s(300, 400), s(3900, 600)], 4096, 512)
    assert lens(got) == [(3900, 600), (300, 400)] or lens(got) == [(3900, 600)]
    assert sthink_replica.choose_samples([s(300, 9)], 4096, 512) == [s(300, 9)]


# ---- the readers on a synthetic record --------------------------------------

def _record(c, steps=True, trace=True):
    span = lambda name, ts, dur, **args: {"name": name, "ph": "X", "ts": 1e6 * ts,
                                          "dur": dur, "pid": 1, "tid": 1, "args": args}
    mine = dict(window_rows=20000, full_rows=26000, wrapped_slots=2, active=6,
                expert_assignments=432, experts_touched=300)
    events = [span("engine.step", 100.5, 12000.0, **mine),
              span("engine.step", 100.6, 12000.0, **mine),
              span("engine.step", 100.7, 500000.0, **{**mine, "prefill_batches": 1}),
              # Command A+'s steps: the two row counters, no `wrapped_slots`
              span("engine.step", 100.3, 6000.0, window_rows=9000, full_rows=39000,
                   active=3, experts_touched=30),
              span("engine.prefill_dispatch", 100.7001, 4000.0, bucket=16384, batch=1,
                   tokens=6000),
              # a pass whose step ends behind the traced seconds: time only
              span("engine.step", 101.9, 900000.0, **{**mine, "prefill_batches": 1}),
              span("engine.prefill_dispatch", 101.9001, 4000.0, bucket=16384, batch=1,
                   tokens=8000)]
    if not steps:
        events = events[3:4] + [{**events[4], "args": {"bucket": 64, "batch": 1}}]
    return {"rows": [], "window_rows": [], "t_open": 100.0, "seconds": 2.0,
            "config": c, "traffic": {"trace_window_s": [0.0, 2.0]},
            "device": {"kind": "TPU v5e"},
            "_program_window": {"traces": [], "steps": [
                e for e in events if e["name"] == "engine.step"]},
            "program_spans": {"events": events, "info": {}},
            "trace": {"module_ms_p50": {"jit_decode_step": 11.0}, "window_s": 2.0,
                      # the prompt kernel's events on the wall clock: the 24 of
                      # the pass of 6,000 tokens (12 layers x 2 windows), 10 of
                      # the 24 of the one that straddles the end
                      "prompt_kernel_events":
                          [[100.71 + 0.01 * i, 0.004] for i in range(24)]
                          + [[101.91 + 0.007 * i, 0.006] for i in range(10)]
                      if trace else [],
                      "kernel_calls": {
                          "flash_attention_banded": [34, 0.156],
                          "gqa_decode_attention": [48, 0.002]}
                      if trace else {"ssd_step": [4, 0.002]}}}


def test_every_new_reader_reads_its_number(c):
    run = _record(c)
    read = lambda name: load_py(os.path.join(
        ROOT, "perfbench", "metrics", name + ".py")).read(run)
    assert read("engine.sthink_step_ms_p50") == 12.0
    assert read("engine.sthink_cache_bytes_per_step") == (9 * 20000 + 3 * 26000) * 2048
    assert read("swa.sthink_wrapped_slots_share") == pytest.approx(100 * 2 / 6)
    assert read("engine.sthink_prefill_us_per_token") == 8000.0 / 14000
    # Command A+'s two readers that the cell's name joined: the same spans
    assert read("engine.swa_step_ms_p50") == 12.0
    assert read("engine.swa_prefill_us_per_token") == 8000.0 / 14000
    assert read("moe.sthink_experts_touched_share") == pytest.approx(100 * 300 / 768)
    need = sc.decode_step_bytes(c, 20000, 26000, 300)
    assert read("kernels.sthink_moe_decode_roofline") == \
        pytest.approx(100 * need / 819e9 / 11e-3)
    # (the MEDIAN step's bytes: one crowded step among the four moves nothing)
    crowded = run["program_spans"]["events"][0]["args"]
    crowded["experts_touched"] = 700
    assert read("kernels.sthink_moe_decode_roofline") == \
        pytest.approx(100 * need / 819e9 / 11e-3)
    crowded["experts_touched"] = 300
    # the pass of 6,000 tokens ran whole inside: its pairs over its own 24
    # events; the one of 8,000 did not
    assert read("kernels.sthink_prefill_roofline") == \
        pytest.approx(100 * sc.attention_flops(c, 6000) / 197e12 / (24 * 0.004))
    run["trace"]["prompt_kernel_events"].pop(3)          # the trace lost a call
    assert read("kernels.sthink_prefill_roofline") is None
    flops = sc.product_flops(c, 6000, head_rows=1) + sc.attention_flops(c, 6000) \
        + 4 * (sc.product_flops(c, 6, 432, head_rows=6)
               + 4 * 28 * 128 * (9 * 20000 + 3 * 26000 + 6 * 12))
    assert read("serve.sthink_window_mfu") == pytest.approx(100 * flops / 197e12 / 2.0)
    run = _record(c)
    for name in NEW_METRICS:   # none may read over 100%
        if name.endswith(("roofline", "share", "mfu")):
            assert 0 < read(name) <= 100, name


def test_the_new_readers_read_nothing_on_another_cells_record(c):
    """A record of another model's cell (Command A+'s steps: `window_rows` and
    `full_rows` but no `wrapped_slots`, as the parent's program reports them;
    no banded kernel's calls; no `tokens` on the prompt passes): every new
    reader returns None and does not raise."""
    run = _record(c, steps=False, trace=False)
    for name in sorted(NEW_METRICS):
        read = load_py(os.path.join(ROOT, "perfbench", "metrics", name + ".py")).read
        assert read(run) is None, name
    run["trace"] = None
    for name in sorted(DEVICE_METRICS):
        read = load_py(os.path.join(ROOT, "perfbench", "metrics", name + ".py")).read
        assert read(run) is None, name
