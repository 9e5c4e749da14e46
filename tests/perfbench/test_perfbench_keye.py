"""The Keye cell: its toy runs through the real command on the CPU from a
throw-away root (untraced, traced, under the `int8` control and under the
`every_row` control, which must come out as not correct: the comparison sees
the mechanism); the manifest's new entries; the configuration file against
the catalog row it was drawn from; the `keye_counts` functions against the
configuration's own `arithmetic`; every new reader on a synthetic span list
and trace, None where there is nothing to read.

The model-configs guide's "shares add up to the whole" test does not apply
here: the configuration cuts depth alone. No expert and no vocabulary row is
left out, so there is no share of a layer whose parts could be summed; the
program against the reference on the whole layer is `tests/test_dsa.py`."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "perfbench", "run.py")
sys.path.insert(0, ROOT)

from perfbench.lib import keye_counts as kc  # noqa: E402
from perfbench.lib.manifest import Manifest, load_py  # noqa: E402

CELL, CONFIG = "keye-vl2-serve-docqa", "keye-vl-2.0-30b-a3b.1of8"
NEW_METRICS = {"engine.dsa_step_ms_p50", "engine.dsa_cache_bytes_per_step",
               "dsa.selected_rows_share", "engine.dsa_prefill_us_per_token",
               "moe.keye_experts_touched_share", "kernels.dsa_moe_decode_hbm_share",
               "kernels.dsa_scores_roofline", "kernels.dsa_rows_roofline",
               "kernels.dsa_select_roofline", "kernels.dsa_attention_roofline"}
DEVICE_METRICS = {m for m in NEW_METRICS if m.startswith("kernels.")}
# the catalog row's `config` (model-configs guide, architectures.jsonl,
# Keye-VL-2.0-30B-A3B), copied here so that the test needs no file outside
# the repo
ROW = {"attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
       "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
       "max_position_embeddings": 262144, "max_window_layers": 48,
       "mlp_only_layers": [], "model_type": "KeyeVL2", "moe_intermediate_size": 768,
       "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 128,
       "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 4,
       "num_local_experts": 128, "rms_norm_eps": 1e-06,
       "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default",
                        "type": "default"},
       "rope_theta": 10000000,
       "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                     "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                     "q_chunk_size": 512, "topk": 2048},
       "sliding_window": None, "tie_word_embeddings": False,
       "use_sliding_window": False, "vocab_size": 151936}
TOY = {**ROW, "head_dim": 16, "hidden_size": 64, "moe_intermediate_size": 32,
       "num_attention_heads": 8, "num_experts": 8, "num_local_experts": 8,
       "num_experts_per_tok": 2, "num_hidden_layers": 3, "num_key_value_heads": 2,
       "rope_theta": 10000, "vocab_size": 96,
       "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                     "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                     "q_chunk_size": 8, "topk": 16},
       "torch_dtype": "float32", "reference": "keye_vl2",
       "run": {"num_slots": 4, "max_len": 128, "prefill_tokens": 64,
               "max_concurrent_queries": 32}}
TRAFFIC = {"kind": "open_loop", "driver": "open_loop_http_keye",
           "rate_per_s": 4.0, "arrival_cv": 1.0, "warm_s": 1,
           "prompt_tokens": {"log_mean": 3.4, "log_sd": 0.5, "min": 12, "max": 60},
           "answer_tokens": {"log_mean": 1.8, "log_sd": 0.4, "min": 2, "max": 12},
           "slot_rule": {"token_gap_ms": 20, "ttft_ms": 30}, "request_timeout_s": 60,
           "warm": {"prefill_buckets": [16, 32, 64], "admission_batches": [1],
                    "attention_buckets": [64, 128]},
           "trace_window_s": [0.5, 1.5], "check_answers": 3,
           "check_decode_steps": 12, "control": "int8",
           "limits": {"token_gap_mean_spacings": 0.01,
                      "prefill_logits_rel_err": 1e-4, "route_margin_max": 1e-4,
                      "select_margin_max": 1e-4}}


def _throw_away_root(tmp_path):
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    extra = tmp_path / "extra"
    for sub in ("configs", "traffic"):
        (extra / sub).mkdir(parents=True)
    (extra / "configs" / "toy-keye.json").write_text(json.dumps(TOY))
    (extra / "traffic" / "toy-docqa.json").write_text(json.dumps(TRAFFIC))
    metrics = {"end_to_end": [], "per_layer": []}
    for kind in metrics:
        for m in real[kind]:
            m = dict(m)
            if "workloads" in m:
                if CELL not in m["workloads"]:
                    continue
                m["workloads"] = ["toy-keye-serve"]
            metrics[kind].append(m)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": real["command"], "paths": ["extra"], "run_seconds": 2,
        "configs": [{"name": "toy-keye", "source": "none",
                     "file": "extra/configs/toy-keye.json", "reduced": [],
                     "why": "throw-away"}],
        "workloads": [{"name": "toy-keye-serve", "config": "toy-keye",
                       "traffic": "toy-docqa", "chips": 1, "why": "throw-away"}],
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
    (0, "every_row", set()),
])
def test_the_keye_toy_runs_through_the_real_command(tmp_path, trace, control, expects):
    """Untraced: the end-to-end metrics; traced: every new metric that reads
    the program's spans reads a number (the four kernels' and the whole
    step's shares read the device's trace, which the CPU has none of, and
    are left out); either control comes out as not correct: `int8` by the
    logits, `every_row` by the logits too (the reference keeps its own
    rows there). Exit 10."""
    args = ["--root", _throw_away_root(tmp_path), "--workload", "toy-keye-serve",
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
                                    "select_margin_max", "token_gap_mean_spacings"}
    if trace:
        m = {k: v["value"] for k, v in rep["metrics"].items()}
        assert m["engine.dsa_step_ms_p50"] > 0
        assert m["engine.dsa_prefill_us_per_token"] > 0
        # at most 4 busy slots of 72 positions: 128 x 4 B a key, 2 x 2 x 16 x 4 B a position
        assert 0 < m["engine.dsa_cache_bytes_per_step"] <= 3 * 4 * (72 * 512 + 16 * 256)
        assert 0 < m["dsa.selected_rows_share"] < 100
        assert 0 < m["moe.keye_experts_touched_share"] <= 100
    if control:
        failing = [l for l in p.stdout.splitlines() if "NOT OK" in l]
        assert any("prefill_logits_rel_err" in l for l in failing), failing
        if control == "every_row":   # its indexer is sound: only the logits say so
            assert not any("select_margin_max" in l for l in failing)


def test_without_a_chip_the_new_cell_gives_no_result():
    p = _run(["--workload", CELL, "--seed", "1", "--seconds", "6", "--trace", "0"])
    assert p.returncode not in (0, 10), p.stdout[-2000:]
    assert "needs a TPU" in p.stdout + p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_the_manifest_takes_the_new_entries():
    man = Manifest(ROOT)
    cell = man.cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "docqa-open-loop" and len(cell["why"]) <= 200
    entry = man.config_entry(CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == \
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json"
    assert man.data["workloads"][-1] == cell and man.data["configs"][-1] == entry
    # every line of text the entries bring: 1 to 200 printable characters (the
    # check refused the configuration's `why` at 203 before any run)
    for e in [cell, entry] + man.data["per_layer"][-len(NEW_METRICS):]:
        for key in ("why", "layer", "source"):
            if key in e:
                assert 1 <= len(e[key]) <= 200 and e[key].isascii() \
                    and e[key].isprintable(), (e["name"], key, len(e[key]))
    e2e ={m["name"] for m in man.metrics_for(CELL, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert NEW_METRICS | {"engine.batch_occupancy", "device.peak_hbm_bytes.serve",
                          "engine.driver_device_wait_share",
                          "engine.wakes_per_token"} <= per_layer
    assert not {"kernels.decode_hbm_share", "kernels.ssd_moe_decode_hbm_share",
                "moe.ssd_experts_touched_share", "engine.eva_step_ms_p50"} & per_layer
    new = man.data["per_layer"][-len(NEW_METRICS):]
    assert {m["name"] for m in new} == NEW_METRICS      # appended, at the end
    for m in new:
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        man.find("metrics", m["name"] + ".py")
    tr = man.load_traffic(cell["traffic"])
    assert tr["kind"] == "open_loop" and tr["arrival_cv"] == 1.0 and tr["warm_s"] == 20
    assert tr["prompt_tokens"]["min"] == 8192 and tr["prompt_tokens"]["max"] >= 16384
    assert tr["answer_tokens"] == {"log_mean": 4.85, "log_sd": 0.5, "min": 64,
                                   "max": 384}
    conf = man.load_config(CONFIG)
    assert tr["prompt_tokens"]["max"] + tr["answer_tokens"]["max"] \
        < conf["run"]["max_len"]
    assert tr["prompt_tokens"]["min"] >= 4 * conf["sa_config"]["topk"]
    assert set(tr["limits"]) == {"token_gap_mean_spacings", "prefill_logits_rel_err",
                                 "route_margin_max", "select_margin_max"}
    assert set(tr["limits_why"]) == set(tr["limits"])
    # the reference follows the program over an answer's whole length, and the
    # served tokens' number is a limit BETWEEN readings: under one spacing
    assert tr["check_decode_steps"] >= tr["answer_tokens"]["max"]
    assert tr["limits"]["token_gap_mean_spacings"] < 1.0
    # the traced seconds hold several prompt passes (rate x seconds >= 4)
    a, b = tr["trace_window_s"]
    assert tr["rate_per_s"] * (b - a) >= 4
    from perfbench.lib import traffic as traffic_mod
    assert traffic_mod.slot_rule(tr, conf["run"]["num_slots"])["ok"]


@pytest.fixture(scope="module")
def c():
    return Manifest(ROOT).load_config(CONFIG)


def test_the_configuration_file_is_the_row_but_for_the_depth(c):
    """Every key of the catalog row is as published but the one reduced key,
    which carries the published value beside it; every expert and the whole
    vocabulary are held."""
    for key, value in ROW.items():
        if key == "num_hidden_layers":
            assert c[key] == 6 and c["source_" + key] == value
        else:
            assert c[key] == value, key
    assert set(c) - set(ROW) == {"torch_dtype", "reference", "source_num_hidden_layers",
                                 "deployment", "arithmetic", "assumed", "run"}
    assert {"q_k_norm", "indexer_inputs", "indexer_key_norm", "indexer_rotary",
            "chunk_sizes", "rotary_layout", "positions", "norm_weights", "dtypes",
            "deployment"} <= set(c["assumed"])
    guesses = [k for k, v in c["assumed"].items() if "first to check" in v]
    assert {"q_k_norm", "indexer_inputs", "indexer_key_norm", "indexer_rotary",
            "chunk_sizes"} <= set(guesses)
    assert c["run"]["num_slots"] == 8 and c["run"]["max_len"] == 32768
    assert c["run"]["control"] == "int8" and 48 % c["num_hidden_layers"] == 0
    pub = json.load(open(os.path.join(ROOT, "tests", "perfbench", "published",
                                      CONFIG + ".json")))
    assert pub["published"]["sa_config"] == ROW["sa_config"]
    assert pub["published"]["num_hidden_layers"] == 48


def test_the_file_makes_the_program_configuration(c):
    from perfbench.lib import keye_model

    cfg = keye_model.model_config(c)
    assert cfg.runs() == (("dsa", 6),) and cfg.run_ffns() == ("moe",)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.rope_theta) == \
        (32, 4, 128, 1e7)
    assert (cfg.dsa_topk, cfg.dsa_heads, cfg.dsa_head_dim, cfg.dsa_chunk) == \
        (2048, 16, 64, 512)
    assert (cfg.n_experts, len(cfg.experts_held), cfg.top_k, cfg.d_expert,
            cfg.n_shared) == (128, 128, 8, 768, 0)
    assert cfg.router == "softmax" and cfg.untied_head and cfg.vocab_size == 151936
    assert keye_model.model_config(c, dsa_topk=32768).dsa_topk == 32768
    with pytest.raises(ValueError, match="sparse-attention stack"):
        keye_model.model_config({**c, "tie_word_embeddings": True})


# ---- counts against the configuration's own arithmetic ---------------------

@pytest.mark.parametrize("fn,want_millions,said", [
    (kc.attn_params, 18.875, "= 18.875M"),
    (kc.indexer_params, 2.261, "= 2.261M"),
    (kc.router_params, 0.262, "= 0.262M"),
    (kc.expert_params, 4.719, "= 4.719M"),
    (kc.layer_params, 625.38, "625.38M"),
    (kc.param_count, 4374.6, "4,374.6M parameters = 8.75 GB"),
])
def test_parameter_counts_are_the_files_arithmetic(c, fn, want_millions, said):
    assert fn(c) / 1e6 == pytest.approx(want_millions, rel=1e-3)
    assert said in c["arithmetic"]


def test_cache_and_step_bytes(c):
    assert kc.kv_position_bytes(c) == 2048 and kc.key_bytes(c) == 256
    assert "2,048 B" in c["arithmetic"] and "256 B" in c["arithmetic"]
    assert kc.cache_bytes(c, 8, 32768) == 262144 * 13824        # "3.62 GB"
    assert kc.cache_bytes(c, 8, 32768) / 1e9 == pytest.approx(3.62, abs=0.01)
    assert kc.held_expert_slots(c) == 768
    assert kc.decode_fixed_weight_bytes(c) / 1e9 == pytest.approx(0.879, abs=0.002)
    # a slot of 16k positions: 16k keys scored, 2,048 positions read, six layers
    assert kc.cache_bytes_per_step(c, 16384, 2048) == 6 * (16384 * 256 + 2048 * 2048)
    assert kc.decode_step_bytes(c, 0, 0, 0) == kc.decode_fixed_weight_bytes(c)
    assert kc.decode_step_bytes(c, 0, 0, 100) - kc.decode_step_bytes(c, 0, 0, 0) \
        == 100 * 2 * 4718592
    assert kc.select_flops(c, 8192) == 2 * 16 * 64 * 8192 * 8193 / 2
    assert kc.chosen_pairs(c, 2048) == 2048 * 2049 / 2
    assert kc.chosen_pairs(c, 8192) == 2048 * 2049 / 2 + 6144 * 2048
    assert kc.attention_flops(c, 8192) == 4 * 32 * 128 * kc.chosen_pairs(c, 8192)


def test_the_program_holds_what_the_arithmetic_says(c):
    """The program's own parameter tree and cache, as shapes."""
    import jax

    from perfbench.lib import keye_model
    from ray_tpu.models import hybrid

    cfg = keye_model.model_config(c)
    params = jax.eval_shape(lambda k: hybrid.init_params(k, cfg), jax.random.PRNGKey(0))
    assert sum(a.size for a in jax.tree_util.tree_leaves(params)) == kc.param_count(c)
    state = jax.eval_shape(lambda: cfg.make_cache(8, 32768).state)
    assert sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(state)) \
        == kc.cache_bytes(c, 8, 32768)


# ---- the readers on a synthetic record --------------------------------------

def _record(c, steps=True, trace=True):
    step = lambda ts, **args: {"name": "engine.step", "ph": "X", "ts": 1e6 * ts,
                               "dur": 6000.0, "pid": 1, "tid": 1, "args": args}
    mine = dict(kv_rows=39000, index_rows=39000, selected_rows=6144, active=3,
                expert_assignments=144, experts_touched=130)
    events = [step(100.5, **mine), step(100.6, **mine),
              step(100.7, **{**mine, "prefill_batches": 1}),
              step(100.8, state_slots=3, kv_rows=700, experts_touched=30),
              {"name": "engine.prefill_dispatch", "ph": "X", "ts": 1e6 * 100.7,
               "dur": 4000.0, "pid": 1, "tid": 1,
               "args": {"bucket": 16384, "batch": 1, "tokens": 16000}}]
    if not steps:
        events = events[3:4] + [{**events[4], "args": {"bucket": 64, "batch": 1}}]
    return {"rows": [], "window_rows": [], "t_open": 100.0, "seconds": 2.0,
            "config": c, "traffic": {"trace_window_s": [0.0, 2.0]},
            "device": {"kind": "TPU v5e"},
            "_program_window": {"traces": [], "steps": [
                e for e in events if e["name"] == "engine.step"]},
            "program_spans": {"events": events, "info": {}},
            "trace": {"module_ms_p50": {"jit_decode_step": 6.0},
                      "kernel_calls": {
                          "dsa_select": [[16384, 0.014]] * 6, "dsa_attention": [[16384, 0.034]] * 6,
                          "dsa_scores": [12, 0.0006], "dsa_rows": [12, 0.0022]}
                      if trace else {"ssd_step": [4, 0.002]}}}


def test_every_new_reader_reads_its_number(c):
    run = _record(c)
    read = lambda name: load_py(os.path.join(
        ROOT, "perfbench", "metrics", name + ".py")).read(run)
    assert read("engine.dsa_step_ms_p50") == 6.0
    assert read("engine.dsa_cache_bytes_per_step") == 6 * (39000 * 256 + 6144 * 2048)
    assert read("dsa.selected_rows_share") == pytest.approx(100 * 6144 / 39000)
    assert read("engine.dsa_prefill_us_per_token") == 4000.0 / 16000
    assert read("moe.keye_experts_touched_share") == pytest.approx(100 * 130 / 768)
    need = kc.decode_step_bytes(c, 39000, 6144, 130)
    assert read("kernels.dsa_moe_decode_hbm_share") == \
        pytest.approx(100 * need / 819e9 / 6e-3)
    assert read("kernels.dsa_scores_roofline") == \
        pytest.approx(100 * 12 * 39000 * 256 / 819e9 / 0.0006)
    assert read("kernels.dsa_rows_roofline") == \
        pytest.approx(100 * 12 * 6144 * 2048 / 819e9 / 0.0022)
    assert read("kernels.dsa_select_roofline") == \
        pytest.approx(100 * kc.select_flops(c, 16384) / 197e12 / 0.014)
    assert read("kernels.dsa_attention_roofline") == \
        pytest.approx(100 * kc.attention_flops(c, 16384) / 197e12 / 0.034)
    for name in NEW_METRICS:   # none may read over 100%
        if name.endswith(("roofline", "share")):
            assert 0 < read(name) <= 100, name


def test_the_new_readers_read_nothing_on_another_cells_record(c):
    """A record of another model's cell (steps with `kv_rows` and the expert
    counters but no `index_rows`; no `dsa_*` kernel calls; no `tokens` on the
    prompt passes): every new reader returns None and does not raise."""
    run = _record(c, steps=False, trace=False)
    for name in sorted(NEW_METRICS):
        read = load_py(os.path.join(ROOT, "perfbench", "metrics", name + ".py")).read
        assert read(run) is None, name
    run["trace"] = None
    for name in sorted(DEVICE_METRICS):
        read = load_py(os.path.join(ROOT, "perfbench", "metrics", name + ".py")).read
        assert read(run) is None, name


def test_kernel_calls_reads_the_positions_from_the_calls_own_shapes():
    from perfbench.lib import keye_replica, xplane

    ops = [("%dsa_select.8 = (s32[16384,1]{1,0}, s32[16384,1]{1,0}) custom-call(...)", 0, 14e6),
           ("%dsa_attention.8 = bf16[4,64,2048,128]{3,2,1,0} custom-call(...)", 0, 34e6),
           ("%dsa_rows.11 = bf16[8,4,8,128]{3,2,1,0} custom-call(...)", 0, 2e5),
           ("%dsa_rows.11 = bf16[8,4,8,128]{3,2,1,0} custom-call(...)", 0, 2e5),
           ("%dsa_scores.3 = f32[8,1,16384]{2,1,0} custom-call(...)", 0, 5e4),
           ("%fusion.1 = f32[8] fusion(%dsa_scores.3)", 0, 1e3)]
    got = keye_replica.kernel_calls({"/device:TPU:0": {xplane.OPS_LINE: ops}}, rep=8)
    assert got == {"dsa_select": [[16384, 0.014]], "dsa_attention": [[16384, 0.034]],
                   "dsa_scores": [1, 5e-5], "dsa_rows": [2, 4e-4]}


# ---- the comparison follows an answer's WHOLE length ------------------------

def test_the_served_tokens_are_held_past_the_eighth(c):
    """What the cell's `check` does, in this process on the toy: the engine
    serves two answers of 12 tokens; with `check_decode_steps` at an answer's
    whole length the reference follows the program's experts and row lists
    at EVERY token served, so `token_gap_mean_spacings`, the one number
    that reads the window's own tokens, is ~0 for the served answers and
    far over the toy's limit once ONE token behind the eighth is not what
    the program computes there, while the three numbers of the check's own
    programs do not move. (Under 8 followed positions the number's floor in
    the cell was 1-9 spacings at any precision: it saw nothing. PERF.md,
    PR 56, second session.)"""
    import jax
    import jax.numpy as jnp  # noqa: F401
    import numpy as np

    from perfbench.lib import keye_model, keye_replica
    from ray_tpu.models.serving import ContinuousBatchingEngine

    toy = dict(TOY)
    cfg = keye_model.model_config(toy)
    params = keye_model.make_params(cfg, 7)
    eng = ContinuousBatchingEngine(params, cfg, num_slots=4, max_len=128)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 96, n).tolist() for n in (40, 27)]
    samples = [{"prompt": p, "answer": eng.generate(p, max_new_tokens=12)[len(p):]}
               for p in prompts]
    assert [len(s["answer"]) for s in samples] == [12, 12]
    ref = load_py(os.path.join(ROOT, "perfbench", "references", "keye_vl2.py"))

    def compared(samples):
        got = keye_replica.program_rows(eng, samples, TRAFFIC["check_decode_steps"])
        assert [len(g["lists"]) for g in got] == [12, 12]      # every served token
        assert [len(g["rows"]) for g in got] == [14, 14]       # whole, half, 12 decoded
        return keye_replica.compare_with_reference(ref, toy, params, samples, got)

    sound = compared(samples)
    assert sound["tokens_compared"] == 24
    for name, limit in TRAFFIC["limits"].items():
        assert sound[name] <= limit, (name, sound[name])
    wrong = [dict(s, answer=list(s["answer"])) for s in samples]
    wrong[0]["answer"][10] = (wrong[0]["answer"][10] + 1) % 96
    turned = compared(wrong)
    assert turned["token_gap_mean_spacings"] > 100 * TRAFFIC["limits"]["token_gap_mean_spacings"]
    assert turned["answers"][0]["off_argmax"] >= 1
    for name in ("route_margin_max", "select_margin_max"):
        assert turned[name] <= TRAFFIC["limits"][name]
    jax.clear_caches()
