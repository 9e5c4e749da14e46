"""The Command A+ cell: its toy runs through the real command on the CPU
from a throw-away root (untraced, traced, under the `int8` control and under
the `no_window` control, both of which must come out as not correct: the
comparison sees the precision and the mechanism); the manifest's new entries,
held by NAME; the configuration file against the catalog row it was drawn
from, published widths both ways; the file's `arithmetic` recomputed from the
leaves by `cmda_counts`; the counts functions against hand sums; the slot
rule; every new reader on a synthetic span list and trace, None where there
is nothing to read; the mixed queue's generator.

The model-configs guide's "shares add up to the whole" test is
`tests/test_swa.py::test_the_eight_shares_add_up_to_the_uncut_layer`."""

import json
import math
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "perfbench", "run.py")
sys.path.insert(0, ROOT)

from perfbench.lib import cmda_counts as cc  # noqa: E402
from perfbench.lib import cmda_traffic  # noqa: E402
from perfbench.lib.manifest import Manifest, load_py  # noqa: E402

CELL, CONFIG = "command-a-plus-serve-mixedqueue", "command-a-plus-05-2026.1of8"
TRAFFIC_NAME = "mixed-queue-open-loop"
NEW_METRICS = {"engine.swa_step_ms_p50", "engine.swa_prefill_us_per_token",
               "engine.swa_cache_bytes_per_step", "swa.window_rows_share",
               "moe.cmda_experts_touched_share", "kernels.swa_prefill_roofline",
               "kernels.swa_decode_roofline", "kernels.swa_moe_decode_roofline",
               "serve.swa_window_mfu"}
DEVICE_METRICS = {m for m in NEW_METRICS if m.startswith(("kernels.", "serve."))}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
# the catalog row's `config` (model-configs guide, architectures.jsonl,
# command-a-plus-05-2026), copied here so that the test needs no file outside
# the repo
ROW = {"attention_bias": False, "expert_selection_fn": "sigmoid",
       "first_k_dense_replace": 0, "head_dim": 128, "hidden_act": "silu",
       "hidden_size": 4096, "intermediate_size": 4096, "layer_norm_eps": 1e-05,
       "layer_switch": 4, "layer_types": PERIOD * 8, "logit_scale": 1,
       "max_position_embeddings": 200000, "model_type": "cohere2_moe",
       "norm_topk_prob": True, "num_attention_heads": 128, "num_experts": 128,
       "num_experts_per_tok": 8, "num_hidden_layers": 32, "num_key_value_heads": 8,
       "num_shared_experts": 4, "order_of_interleaved_layers": "local_attn_first",
       "position_embedding_type": "rope_gptj", "prefix_dense_intermediate_size": 16384,
       "prefix_dense_sliding_window_pattern": 1, "rms_norm_eps": None,
       "rope_parameters": {"rope_theta": 50000, "rope_type": "default"},
       "rope_theta": 50000, "rotary_pct": 1,
       "shared_expert_combination_strategy": "average", "sliding_window": 4096,
       "tf_legacy_loss": False, "tie_word_embeddings": True,
       "use_embedding_sharing": True, "use_gated_activation": True,
       "use_parallel_block": True, "use_parallel_embedding": False,
       "use_qk_norm": False, "vocab_size": 262144}
REDUCED = {"num_hidden_layers": 4, "layer_types": PERIOD, "num_experts": 16,
           "vocab_size": 32768}
TOY = {**ROW, "head_dim": 16, "hidden_size": 64, "intermediate_size": 32,
       "num_attention_heads": 8, "num_key_value_heads": 2, "num_experts": 4,
       "experts_held": {"of": 8, "first": 0, "count": 4}, "num_experts_per_tok": 2,
       "num_shared_experts": 2, "num_hidden_layers": 4, "layer_types": PERIOD,
       "sliding_window": 16, "vocab_size": 96, "torch_dtype": "float32",
       "reference": "command_a_plus",
       "run": {"num_slots": 4, "max_len": 128, "max_concurrent_queries": 32}}
TRAFFIC = {"kind": "open_loop", "driver": "open_loop_http_cmda",
           "rate_per_s": 4.0, "arrival_cv": 1.0, "warm_s": 1,
           "mix": [{"name": "short", "share": 0.6,
                    "prompt_tokens": {"log_mean": 2.2, "log_sd": 0.4, "min": 4, "max": 16}},
                   {"name": "long", "share": 0.4,
                    "prompt_tokens": {"log_mean": 4.2, "log_sd": 0.3, "min": 50, "max": 100}}],
           "prompt_tokens": {"log_mean": 3.0, "log_sd": 1.0, "min": 4, "max": 100},
           "answer_tokens": {"log_mean": 1.8, "log_sd": 0.4, "min": 2, "max": 12},
           "slot_rule": {"token_gap_ms": 20, "ttft_ms": 30}, "request_timeout_s": 60,
           "warm": {"prefill_buckets": [16, 128], "admission_batches": [1],
                    "attention_buckets": [64, 128]},
           "trace_window_s": [0.0, 2.0], "check_answers": 1000,
           "check_decode_steps": 12, "control": "int8",
           "limits": {"token_gap_mean_spacings": 0.01,
                      "prefill_logits_rel_err": 1e-4, "route_margin_max": 1e-4}}


def _throw_away_root(tmp_path):
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    extra = tmp_path / "extra"
    for sub in ("configs", "traffic"):
        (extra / sub).mkdir(parents=True)
    (extra / "configs" / "toy-cmda.json").write_text(json.dumps(TOY))
    (extra / "traffic" / "toy-mixed.json").write_text(json.dumps(TRAFFIC))
    metrics = {"end_to_end": [], "per_layer": []}
    for kind in metrics:
        for m in real[kind]:
            m = dict(m)
            if "workloads" in m:
                if CELL not in m["workloads"]:
                    continue
                m["workloads"] = ["toy-cmda-serve"]
            metrics[kind].append(m)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": real["command"], "paths": ["extra"], "run_seconds": 2,
        "configs": [{"name": "toy-cmda", "source": "none",
                     "file": "extra/configs/toy-cmda.json", "reduced": [],
                     "why": "throw-away"}],
        "workloads": [{"name": "toy-cmda-serve", "config": "toy-cmda",
                       "traffic": "toy-mixed", "chips": 1, "why": "throw-away"}],
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
    (1, None, (NEW_METRICS - DEVICE_METRICS) | {"engine.batch_occupancy", "compile.s",
                                                "engine.wakes_per_token"}),
    (0, "int8", set()),
    (0, "no_window", set()),
])
def test_the_cmda_toy_runs_through_the_real_command(tmp_path, trace, control, expects):
    """Untraced: the end-to-end metrics; traced: every new metric that reads
    the program's spans reads a number (the three kernels' shares and the
    window's read the device's trace, which the CPU has none of, and are left
    out); either control comes out as not correct BY THE LOGITS: `int8` for
    its precision, `no_window` (every layer full, the reference keeping the
    published band) for the mechanism (behind the first window layer the program's
    hidden rows are another function's, so its later routers choose by other
    scores too). Exit 10."""
    args = ["--root", _throw_away_root(tmp_path), "--workload", "toy-cmda-serve",
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
    # one short and one long answer were compared, the long one past 3 windows
    answers = [json.loads(l.split("answer: ", 1)[1].replace("'", '"'))
               for l in p.stdout.splitlines() if "[correct] answer: " in l]
    assert len(answers) == 2
    assert answers[0]["prompt_len"] <= 16 < 3 * 16 < answers[1]["prompt_len"]
    if trace:
        m = {k: v["value"] for k, v in rep["metrics"].items()}
        assert m["engine.swa_step_ms_p50"] > 0
        assert m["engine.swa_prefill_us_per_token"] > 0
        # at most 4 busy slots of 112 positions, 2 x 2 x 16 x 4 B a row
        assert 0 < m["engine.swa_cache_bytes_per_step"] <= 4 * (112 + 3 * 16) * 256
        assert 25 < m["swa.window_rows_share"] < 100
        assert 0 < m["moe.cmda_experts_touched_share"] <= 100
    if control:
        failing = [l for l in p.stdout.splitlines() if "NOT OK" in l]
        assert any("prefill_logits_rel_err" in l for l in failing), failing


def test_without_a_chip_the_new_cell_gives_no_result():
    p = _run(["--workload", CELL, "--seed", "1", "--seconds", "6", "--trace", "0"])
    assert p.returncode not in (0, 10), p.stdout[-2000:]
    assert "needs a TPU" in p.stdout + p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_the_manifest_takes_the_new_entries():
    """Held by NAME, never by place (a later PR appends behind them)."""
    man = Manifest(ROOT)
    cell = man.cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == TRAFFIC_NAME and len(cell["why"]) <= 200
    entry = man.config_entry(CONFIG)
    assert entry["reduced"] == list(REDUCED)
    assert entry["source"] == \
        "https://huggingface.co/CohereLabs/command-a-plus-05-2026/blob/main/config.json"
    new = [m for m in man.data["per_layer"] if m["name"] in NEW_METRICS]
    assert {m["name"] for m in new} == NEW_METRICS and len(new) == len(NEW_METRICS)
    for e in [cell, entry] + new:
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and e[key].isascii() \
                    and e[key].isprintable(), (e["name"], key, len(e[key]))
    for m in new:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        assert m["unit"] in ("ms", "us", "bytes", "%")
        man.find("metrics", m["name"] + ".py")
    e2e = {m["name"] for m in man.metrics_for(CELL, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert NEW_METRICS | {"engine.batch_occupancy", "device.peak_hbm_bytes.serve",
                          "engine.driver_device_wait_share",
                          "engine.wakes_per_token"} <= per_layer
    assert not {"kernels.decode_hbm_share", "dsa.selected_rows_share",
                "moe.ssd_experts_touched_share", "engine.eva_step_ms_p50"} & per_layer
    # every list that Keye's cell joined took this cell's name too, behind it
    for m in man.data["end_to_end"] + man.data["per_layer"]:
        lst = m.get("workloads", [])
        if "keye-vl2-serve-docqa" in lst and len(lst) > 1:
            assert lst[-1] == CELL, m["name"]
    tr = man.load_traffic(TRAFFIC_NAME)
    assert tr["kind"] == "open_loop" and tr["arrival_cv"] == 1.0 and tr["warm_s"] == 20
    short, long_ = tr["mix"]
    assert (short["share"], long_["share"]) == (0.65, 0.35)
    assert short["prompt_tokens"]["min"] == 256 and short["prompt_tokens"]["max"] == 3072
    assert long_["prompt_tokens"]["min"] == 8192 and long_["prompt_tokens"]["max"] == 48640
    assert tr["answer_tokens"] == {"log_mean": 4.85, "log_sd": 0.5, "min": 64,
                                   "max": 384}
    conf = man.load_config(CONFIG)
    assert long_["prompt_tokens"]["max"] + tr["answer_tokens"]["max"] \
        < conf["run"]["max_len"] - 2
    assert short["prompt_tokens"]["max"] < conf["sliding_window"] \
        < long_["prompt_tokens"]["min"]
    assert set(tr["limits"]) == {"token_gap_mean_spacings", "prefill_logits_rel_err",
                                 "route_margin_max"}
    assert set(tr["limits_why"]) == set(tr["limits"])
    assert tr["check_decode_steps"] >= tr["answer_tokens"]["max"]
    assert set(tr["controls"]) == {"int8", "no_window"}
    a, b = tr["trace_window_s"]
    assert tr["rate_per_s"] * (b - a) >= 4
    assert f"{tr['rate_per_s']:g} req/s" in cell["why"]


def test_the_slot_rule_holds_at_the_cells_rate():
    from perfbench.lib import traffic as traffic_mod

    man = Manifest(ROOT)
    tr, conf = man.load_traffic(TRAFFIC_NAME), man.load_config(CONFIG)
    rule = traffic_mod.slot_rule(tr, conf["run"]["num_slots"])
    assert rule["ok"] and tr["rate_per_s"] <= rule["max_rate_per_s"]
    assert not traffic_mod.slot_rule(dict(tr, rate_per_s=4 * rule["max_rate_per_s"]),
                                     conf["run"]["num_slots"])["ok"]


@pytest.fixture(scope="module")
def c():
    return Manifest(ROOT).load_config(CONFIG)


def test_the_configuration_file_is_the_row_but_for_the_cut(c):
    """Every key of the catalog row is as published but the four reduced
    keys, each of which carries the published value beside it; published
    widths both ways (no width of the row is missing from the file, none of
    the file's differs)."""
    for key, value in ROW.items():
        if key in REDUCED:
            assert c[key] == REDUCED[key] and c["source_" + key] == value, key
        else:
            assert c[key] == value, key
    assert set(c) - set(ROW) == {
        "torch_dtype", "reference", "experts_held", "reduced", "deployment",
        "arithmetic", "assumed", "run"} | {"source_" + k for k in REDUCED}
    assert set(c["reduced"]) == set(REDUCED)
    assert c["experts_held"] == {"of": 128, "first": 0, "count": 16}
    assert {"shared_experts", "router", "window", "expert_width", "dense_prefix",
            "dtypes"} <= {k for k, v in c["assumed"].items() if "first to check" in v}
    assert c["run"]["num_slots"] == 12 and c["run"]["max_len"] == 49152
    assert c["run"]["max_len"] % c["sliding_window"] == 0
    pub = json.load(open(os.path.join(ROOT, "tests", "perfbench", "published",
                                      CONFIG + ".json")))["published"]
    for key, value in pub.items():
        assert ROW[key] == value, key
    widths = {"hidden_size", "intermediate_size", "prefix_dense_intermediate_size",
              "head_dim", "num_attention_heads", "num_key_value_heads",
              "num_experts_per_tok", "num_shared_experts", "sliding_window"}
    assert widths | set(REDUCED) == set(pub)


def test_the_file_makes_the_program_configuration(c):
    from perfbench.lib import cmda_model

    cfg = cmda_model.model_config(c)
    assert cfg.runs() == (("swa", 3), ("full", 1)) and cfg.run_ffns() == ("moe", "moe")
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rope_theta,
            cfg.swa_window) == (128, 8, 128, 5e4, 4096)
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k, cfg.d_expert, cfg.n_shared) \
        == (128, tuple(range(16)), 8, 4096, 4)
    assert cfg.router == "sigmoid" and cfg.route_scale == 1.0 and cfg.renormalize
    assert cfg.vocab_size == 32768 and cfg.windowed and not cfg.untied_head
    every = cmda_model.model_config(c, swa_layers=(), full_layers=(1, 2, 3, 4))
    assert every.runs() == (("full", 4),)          # the `no_window` control's stack
    with pytest.raises(ValueError, match="window-and-full stack"):
        cmda_model.model_config({**c, "use_parallel_block": False})


# ---- counts against the configuration's own arithmetic ---------------------

@pytest.mark.parametrize("fn,want_millions,said", [
    (cc.attn_params, 142.606, "= 142.606M"),
    (cc.router_params, 0.524, "= 0.524M"),
    (cc.expert_params, 50.332, "= 50.332M"),
    (cc.shared_params, 201.327, "201.327M"),
    (cc.fixed_layer_params, 344.461, "344.461M outside its routed experts"),
    (cc.layer_params, 1149.768, "1,149.768M = 2.300 GB"),
    (cc.param_count, 4733.293, "4,733.293M parameters = 9.467 GB"),
])
def test_parameter_counts_are_the_files_arithmetic(c, fn, want_millions, said):
    assert fn(c) / 1e6 == pytest.approx(want_millions, abs=6e-4)
    assert said in c["arithmetic"]


def test_cache_and_step_bytes_against_hand_sums(c):
    assert cc.layer_kinds(c) == (3, 1) and cc.row_bytes(c) == 4096
    assert "4,096 B" in c["arithmetic"] and "61,440 rows = 251.658 MB" in c["arithmetic"]
    assert cc.slot_rows(c, 49152) == 49152 + 3 * 4096 == 61440
    assert cc.cache_bytes(c, 12, 49152) == 12 * 61440 * 4096        # "3.020 GB"
    assert cc.cache_bytes(c, 12, 49152) / 1e9 == pytest.approx(3.020, abs=0.001)
    assert (2 * cc.param_count(c) + cc.cache_bytes(c, 12, 49152)) / 1e9 \
        == pytest.approx(12.487, abs=0.001) and "12.487 GB = 78%" in c["arithmetic"]
    assert 4 * 49152 * 4096 / 1e6 == pytest.approx(805.306, abs=0.001)   # all full
    assert cc.held_expert_slots(c) == 64
    # a slot at 20,000 positions: a window layer reads 4,096 rows, the full one all
    assert cc.rows_per_step(c, 4096, 20000) == 3 * 4096 + 20000
    assert cc.cache_bytes_per_step(c, 4096, 20000) == (3 * 4096 + 20000) * 4096
    fixed = 2 * (4 * (142606336 + 524288 + 201326592) + 32768 * 4096)
    assert cc.decode_fixed_weight_bytes(c) == fixed
    assert cc.decode_step_bytes(c, 0, 0, 0) == fixed
    assert cc.decode_step_bytes(c, 0, 0, 10) - fixed == 10 * 2 * 50331648
    # pairs: a window layer min(t + 1, W) keys a query, a full layer t + 1
    assert cc.attended_pairs(c, 1000) == (1000 * 1001 / 2, 1000 * 1001 / 2)
    assert cc.attended_pairs(c, 20000) == (4096 * 4097 / 2 + 15904 * 4096,
                                           20000 * 20001 / 2)
    assert cc.attention_flops(c, 20000) == 4 * 128 * 128 * (
        3 * (4096 * 4097 / 2 + 15904 * 4096) + 20000 * 20001 / 2)
    # products: 2 x (fixed a layer x layers + landed experts) a token; the
    # even share of 8 choices over 16 of 128 experts is ONE assignment a layer
    per_layer = 142606336 + 524288 + 201326592
    assert cc.product_flops(c, 1) == 2 * (4 * per_layer + 4 * 50331648)
    assert cc.product_flops(c, 10, 7, head_rows=10) == 2 * (
        10 * 4 * per_layer + 7 * 50331648 + 10 * 32768 * 4096)


def test_the_program_holds_what_the_arithmetic_says(c):
    """The program's own parameter tree and cache, as shapes: the window
    layers' cache is 4,096 rows a slot, not `max_len`."""
    import jax

    from perfbench.lib import cmda_model
    from ray_tpu.models import hybrid

    cfg = cmda_model.model_config(c)
    params = jax.eval_shape(lambda k: hybrid.init_params(k, cfg), jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) == cc.param_count(c)
    state = jax.eval_shape(lambda: cfg.make_cache(12, 49152).state)
    assert state["wk"].shape == (3, 12, 8, 4096, 128)
    assert state["k"].shape == (1, 12, 8, 49152, 128)
    assert sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(state)) \
        == cc.cache_bytes(c, 12, 49152)


# ---- the mixed queue ---------------------------------------------------------

def test_the_mixed_queue_offers_every_seed_the_same_work():
    tr = Manifest(ROOT).load_traffic(TRAFFIC_NAME)
    big = 2**31 + 11
    a, a2, b = (cmda_traffic.open_loop(tr, s, 51, 32768) for s in (big, big, 7))
    assert a == a2 and a != b
    window = lambda rows: [r for r in rows if 0 <= r["due_s"] < 51]
    n = round(tr["rate_per_s"] * 51)
    assert len(window(a)) == len(window(b)) == n
    for key in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"]):
        assert sorted(map(key, window(a))) == sorted(map(key, window(b)))
    lens = sorted(len(r["prompt"]) for r in window(a))
    n_long = round(0.35 * n)
    assert cmda_traffic.counts(tr, n) == [n - n_long, n_long]
    assert all(256 <= x <= 3072 for x in lens[:n - n_long])
    assert all(8192 <= x <= 48640 for x in lens[n - n_long:])
    assert any(x > 3 * 4096 for x in lens)        # the check's long sample
    assert all(1 <= t < 32768 for r in a[:3] for t in r["prompt"][:50])
    # what is due before the window is the window's own end, a period earlier
    before = [r for r in a if r["due_s"] < 0]
    tail = [r for r in a if r["due_s"] >= 51 - tr["warm_s"]]
    assert [len(r["prompt"]) for r in before] == [len(r["prompt"]) for r in tail]
    # the period's seam lies in its longest gap: the last request of the
    # window is due ln(2 n) / rate seconds before it closes, under every seed
    for rows in (a, b):
        due = [r["due_s"] for r in window(rows)]
        gaps = [y - x for x, y in zip(due, due[1:])] + [51 - due[-1]]
        assert gaps[-1] == max(gaps) and due[0] == 0.0
        assert gaps[-1] == pytest.approx(math.log(2 * n) / tr["rate_per_s"], rel=0.05)
    # without `mix` it is the one-kind generator
    from perfbench.lib import traffic as one_kind
    plain = {k: v for k, v in tr.items() if k != "mix"}
    assert cmda_traffic.open_loop(plain, 3, 51, 99) == one_kind.open_loop(plain, 3, 51, 99)


def test_choose_samples_takes_one_short_and_one_long_past_three_windows():
    from perfbench.lib import cmda_replica

    s = lambda n: {"prompt": [1] * n, "answer": [2]}
    got = cmda_replica.choose_samples(
        [s(9000), s(300), s(40000), s(1000), s(13000), s(2000), s(12500)], 4096)
    assert [len(x["prompt"]) for x in got] == [1000, 12500]
    got = cmda_replica.choose_samples([s(9000), s(300), s(11000)], 4096)
    assert [len(x["prompt"]) for x in got] == [300, 11000]       # none past three
    assert cmda_replica.choose_samples([s(300)], 4096) == [s(300)]


# ---- the readers on a synthetic record --------------------------------------

def _record(c, steps=True, trace=True):
    span = lambda name, ts, dur, **args: {"name": name, "ph": "X", "ts": 1e6 * ts,
                                          "dur": dur, "pid": 1, "tid": 1, "args": args}
    mine = dict(window_rows=9000, full_rows=39000, active=3, expert_assignments=14,
                experts_touched=11)
    events = [span("engine.step", 100.5, 8000.0, **mine),
              span("engine.step", 100.6, 8000.0, **mine),
              span("engine.step", 100.7, 900000.0, **{**mine, "prefill_batches": 1}),
              span("engine.step", 100.3, 6000.0, state_slots=3, kv_rows=700,
                   experts_touched=30),
              span("engine.prefill_dispatch", 100.7001, 4000.0, bucket=49152, batch=1,
                   tokens=16000),
              # two prompts of one bucket admitted in one step: ONE span
              span("engine.step", 100.2, 90000.0, **{**mine, "prefill_batches": 1}),
              span("engine.prefill_dispatch", 100.2001, 4000.0, bucket=1024, batch=2,
                   tokens=1500),
              # a pass whose step ends behind the traced seconds: time only
              span("engine.step", 101.9, 900000.0, **{**mine, "prefill_batches": 1}),
              span("engine.prefill_dispatch", 101.9001, 4000.0, bucket=49152, batch=1,
                   tokens=30000)]
    if not steps:
        events = events[3:4] + [{**events[4], "args": {"bucket": 64, "batch": 1}}]
    return {"rows": [], "window_rows": [], "t_open": 100.0, "seconds": 2.0,
            "config": c, "traffic": {"trace_window_s": [0.0, 2.0]},
            "device": {"kind": "TPU v5e"},
            "_program_window": {"traces": [], "steps": [
                e for e in events if e["name"] == "engine.step"]},
            "program_spans": {"events": events, "info": {}},
            "trace": {"module_ms_p50": {"jit_decode_step": 7.0}, "window_s": 2.0,
                      # the prompt kernel's events on the wall clock: 8 under the
                      # step of two prompts, the 16 of the pass of 16,000 tokens
                      # (4 layers x 4 windows), 12 of the 32 of the one that
                      # straddles the end
                      "prompt_kernel_events":
                          [[100.21 + 0.001 * i, 0.0005] for i in range(8)]
                          + [[100.71 + 0.05 * i, 0.05] for i in range(16)]
                          + [[101.91 + 0.007 * i, 0.006] for i in range(12)]
                      if trace else [],
                      "kernel_calls": {
                          "flash_attention_banded": [36, 0.876],
                          "gqa_decode_attention": [16, 0.002]}
                      if trace else {"ssd_step": [4, 0.002]}}}


def test_every_new_reader_reads_its_number(c):
    run = _record(c)
    read = lambda name: load_py(os.path.join(
        ROOT, "perfbench", "metrics", name + ".py")).read(run)
    assert read("engine.swa_step_ms_p50") == 8.0
    assert read("engine.swa_cache_bytes_per_step") == (3 * 9000 + 39000) * 4096
    assert read("swa.window_rows_share") == pytest.approx(100 * 66000 / (4 * 39000))
    assert read("engine.swa_prefill_us_per_token") == 12000.0 / 47500
    assert read("moe.cmda_experts_touched_share") == pytest.approx(100 * 11 / 64)
    need = cc.decode_step_bytes(c, 9000, 39000, 11)
    assert read("kernels.swa_moe_decode_roofline") == \
        pytest.approx(100 * need / 819e9 / 7e-3)
    # (the MEDIAN step's bytes: one crowded step among the five moves nothing)
    crowded = run["program_spans"]["events"][0]["args"]
    crowded["experts_touched"] = 60
    assert read("kernels.swa_moe_decode_roofline") == \
        pytest.approx(100 * need / 819e9 / 7e-3)
    crowded["experts_touched"] = 11
    assert read("kernels.swa_decode_roofline") == \
        pytest.approx(100 * 16 * (66000 / 4) * 4096 / 819e9 / 0.002)
    # the pass of 16,000 tokens ran whole inside and alone under its span: its
    # pairs over its own 16 events; the one of 30,000 did not, and the span of
    # two prompts says neither their pairs nor their calls
    assert read("kernels.swa_prefill_roofline") == \
        pytest.approx(100 * cc.attention_flops(c, 16000) / 197e12 / (16 * 0.05))
    run["trace"]["prompt_kernel_events"].pop(9)          # the trace lost a call
    assert read("kernels.swa_prefill_roofline") is None
    # the whole window's share counts the two prompts at the least their pairs
    # can be: the even split
    flops = cc.product_flops(c, 16000, head_rows=1) + cc.attention_flops(c, 16000) \
        + cc.product_flops(c, 1500, head_rows=2) + 2 * cc.attention_flops(c, 750) \
        + 5 * (cc.product_flops(c, 3, 14, head_rows=3)
               + 4 * 128 * 128 * (66000 + 3 * 4))
    assert read("serve.swa_window_mfu") == pytest.approx(100 * flops / 197e12 / 2.0)
    run = _record(c)
    for name in NEW_METRICS:   # none may read over 100%
        if name.endswith(("roofline", "share", "mfu")):
            assert 0 < read(name) <= 100, name


def test_the_new_readers_read_nothing_on_another_cells_record(c):
    """A record of another model's cell (steps with `kv_rows` and the expert
    counters but no `window_rows`; no banded kernel's calls; no `tokens` on
    the prompt passes): every new reader returns None and does not raise."""
    run = _record(c, steps=False, trace=False)
    for name in sorted(NEW_METRICS):
        read = load_py(os.path.join(ROOT, "perfbench", "metrics", name + ".py")).read
        assert read(run) is None, name
    run["trace"] = None
    for name in sorted(DEVICE_METRICS):
        read = load_py(os.path.join(ROOT, "perfbench", "metrics", name + ".py")).read
        assert read(run) is None, name


def test_kernel_calls_counts_the_two_kernels_events():
    from perfbench.lib import cmda_replica, xplane

    ops = [("%flash_attention_banded.3 = (bf16[128,4096,128]{2,1,0}, f32[128,8,4096]{2,1,0}) custom-call(...)", 0, 9e6),
           ("%flash_attention_banded.4 = (bf16[128,4096,128]{2,1,0}, f32[128,8,4096]{2,1,0}) custom-call(...)", 0, 3e7),
           ("%gqa_decode_attention.11 = bf16[12,8,16,128]{3,2,1,0} custom-call(...)", 0, 8e4),
           ("%fusion.1 = f32[8] fusion(%gqa_decode_attention.11)", 0, 1e3)]
    got = cmda_replica.kernel_calls({"/device:TPU:0": {xplane.OPS_LINE: ops}})
    assert got == {"flash_attention_banded": [2, pytest.approx(0.039)],
                   "gqa_decode_attention": [1, pytest.approx(8e-5)]}


def test_the_prompt_kernels_events_are_put_on_the_wall_clock():
    """A trace counts from its own start. The host's step spans are in it and
    on the wall clock: the stretch of the wall clock's steps whose durations
    fit the traced ones gives the start, and the device events their place."""
    from perfbench.lib import cmda_replica, xplane

    took = [0.005, 0.9, 0.006, 0.0052, 0.31, 0.0049, 0.005, 0.62, 0.0051]
    steps, at = [], 1000.0
    for d in took:                                    # back to back from 1000 s
        steps.append((at, at + d, {}))
        at += d + 0.0002
    # the trace began 1 ms before the fourth step and holds four steps
    zero = steps[3][0] - 0.001
    host = [("bench.prefill", 0, 1e3)] + [
        (cmda_replica.STEP_SPAN, 1e9 * (a - zero), 1e9 * (b - a) - 3e3)
        for a, b, _ in steps[3:7]]
    ops = [("%flash_attention_banded.3 = bf16[128,4096,128]{2,1,0} custom-call(...)",
            1e9 * (steps[4][0] - zero) + 2e6, 9e6),
           ("%fusion.1 = f32[8] fusion(...)", 5e6, 1e3)]
    planes = {"/device:TPU:0": {xplane.OPS_LINE: ops}, "/host:CPU": {"thread": host}}
    (t, seconds), = cmda_replica.wall_clock_events(planes, steps, "flash_attention_banded")
    assert t == pytest.approx(steps[4][0] + 0.002, abs=1e-6) and seconds == pytest.approx(0.009)
    assert cmda_replica.wall_clock_events(planes, steps[:3], "flash_attention_banded") == []
    assert cmda_replica.wall_clock_events(
        {"/host:CPU": {"thread": host}}, steps, "flash_attention_banded") == []


def test_the_no_window_controls_weights_are_cut_into_the_published_runs():
    import numpy as np

    from perfbench.lib import cmda_replica

    run = {"mixer_norm": np.arange(8.0).reshape(4, 2),
           "full": {"wq": np.arange(24.0).reshape(4, 2, 3)},
           "moe": {"router": np.arange(4.0)}}
    want = {k: (v.copy() if not isinstance(v, dict) else
                {n: a.copy() for n, a in v.items()}) for k, v in run.items()}
    got = cmda_replica.as_published(run, (("swa", 3), ("full", 1)))
    assert not run                                  # every whole leaf was dropped
    assert [sorted(p) for p in got] == [["mixer_norm", "moe", "swa"],
                                        ["full", "mixer_norm", "moe"]]
    np.testing.assert_array_equal(got[0]["swa"]["wq"], want["full"]["wq"][:3])
    np.testing.assert_array_equal(got[1]["full"]["wq"], want["full"]["wq"][3:])
    np.testing.assert_array_equal(got[1]["moe"]["router"], want["moe"]["router"][3:])
