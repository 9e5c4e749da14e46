"""The openPangu-Ultra-MoE cell: its toy runs through the real command on
the CPU from a throw-away root (drafting on, both positions and the module
compared with the reference); the manifest's new entries; the configuration
file against the catalog row it was drawn from; the `pangu_counts`
functions against the hand arithmetic of the issue that added the cell."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "perfbench", "run.py")
sys.path.insert(0, ROOT)

from perfbench.lib import pangu_counts as pc  # noqa: E402
from perfbench.lib.manifest import Manifest  # noqa: E402

CELL = "openpangu-serve-longctx"
CONFIG = "openpangu-ultra-moe-718b.1of32"
# the catalog row's `config` (model-configs guide, architectures.jsonl,
# openPangu-Ultra-MoE-718B), copied here so that the test needs no file
# outside the repo
ROW = {"attention_bias": False, "first_k_dense_replace": 3, "hidden_act": "silu",
       "hidden_size": 7680, "intermediate_size": 18432, "kv_lora_rank": 512,
       "max_position_embeddings": 131072, "model_type": "pangu_ultra_moe",
       "moe_intermediate_size": 2048, "n_routed_experts": 256,
       "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 128,
       "num_experts_per_tok": 8, "num_hidden_layers": 61,
       "num_key_value_heads": 128, "num_nextn_predict_layers": 1,
       "q_lora_rank": 1536, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
       "rms_norm_eps": 1e-05, "rope_theta": 25600000,
       "routed_scaling_factor": 2.5, "sandwich_norm": True,
       "tie_word_embeddings": False, "v_head_dim": 128, "vocab_size": 153600}
REDUCED = {"num_hidden_layers": 6, "first_k_dense_replace": 1,
           "n_routed_experts": 8, "vocab_size": 19200}
TOY = {"first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 128,
       "kv_lora_rank": 32, "moe_intermediate_size": 32, "n_routed_experts": 8,
       "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 8,
       "num_experts_per_tok": 2, "num_hidden_layers": 3,
       "num_nextn_predict_layers": 1, "q_lora_rank": 24, "qk_nope_head_dim": 16,
       "qk_rope_head_dim": 8, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
       "routed_scaling_factor": 2.5, "sandwich_norm": True, "v_head_dim": 16,
       "vocab_size": 16, "torch_dtype": "float32",
       "experts_held": {"of": 8, "first": 0, "count": 8},
       "reference": "openpangu_ultra_moe",
       "run": {"num_slots": 4, "max_len": 128, "prefill_tokens": 128,
               "max_concurrent_queries": 32}}
TRAFFIC = {"kind": "open_loop", "driver": "open_loop_http_pangu",
           "rate_per_s": 4.0, "arrival_cv": 1.0, "warm_s": 1,
           "prompt_tokens": {"log_mean": 2.5, "log_sd": 0.5, "min": 4, "max": 40},
           "answer_tokens": {"log_mean": 2.2, "log_sd": 0.3, "min": 5, "max": 14},
           "slot_rule": {"token_gap_ms": 20, "ttft_ms": 30}, "request_timeout_s": 60,
           "warm": {"prefill_buckets": [8, 16, 32, 64], "admission_batches": [1, 2, 4],
                    "attention_buckets": [64, 128]},
           "trace_window_s": [0.5, 1.5], "check_answers": 3,
           "check_decode_steps": 4, "control": "int8",
           # a vocabulary of 16 puts the top two logits far apart: the token
           # gap says nothing here and is left wide
           "limits": {"token_gap_mean_spacings": 1e6,
                      "prefill_logits_rel_err": 1e-4, "mtp_logits_rel_err": 1e-4,
                      "route_margin_max": 1e-4}}


def _throw_away_root(tmp_path):
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    extra = tmp_path / "extra"
    for sub in ("configs", "traffic"):
        (extra / sub).mkdir(parents=True)
    (extra / "configs" / "toy-pangu.json").write_text(json.dumps(TOY))
    (extra / "traffic" / "toy-longctx.json").write_text(json.dumps(TRAFFIC))
    metrics = {"end_to_end": [], "per_layer": []}
    for kind in metrics:
        for m in real[kind]:
            m = dict(m)
            if "workloads" in m:
                if CELL not in m["workloads"]:
                    continue
                m["workloads"] = ["toy-pangu-serve"]
            metrics[kind].append(m)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": real["command"], "paths": ["extra"], "run_seconds": 2,
        "configs": [{"name": "toy-pangu", "source": "none",
                     "file": "extra/configs/toy-pangu.json", "reduced": [],
                     "why": "throw-away"}],
        "workloads": [{"name": "toy-pangu-serve", "config": "toy-pangu",
                       "traffic": "toy-longctx", "chips": 1, "why": "throw-away"}],
        **metrics}))
    return str(tmp_path)


def _run(args, timeout=400):
    return subprocess.run([sys.executable, RUN] + args, capture_output=True,
                          text=True, timeout=timeout, cwd=ROOT,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))


def _would_report(stdout):
    line = next(l for l in stdout.splitlines() if "would report: " in l)
    return json.loads(line.split("would report: ", 1)[1])


@pytest.mark.parametrize("trace,control,expects", [
    (0, None, {"serve_tokens_per_s", "setup_s"}),
    (1, None, {"spec.accepted_share", "spec.tokens_per_slot_step",
               "engine.mtp_step_ms_p50", "moe.pangu_tokens_per_held_expert",
               "engine.latent_bytes_per_step", "engine.batch_occupancy"}),
    (0, "int8", set()),
])
def test_the_pangu_toy_runs_through_the_real_command(tmp_path, trace, control,
                                                     expects):
    """Untraced: the end-to-end metrics; traced: the new counters' metrics
    read numbers (the two device-trace ones read nothing on the CPU and are
    left out); the int8 control comes out as not correct. Exit 10."""
    args = ["--root", _throw_away_root(tmp_path), "--workload", "toy-pangu-serve",
            "--seed", str(2**31 + 11), "--seconds", "2", "--trace", str(trace),
            "--cpu-rehearsal"] + (["--control", control] if control else [])
    p = _run(args)
    assert p.returncode == 10, p.stdout[-3000:] + p.stderr[-3000:]
    rep = _would_report(p.stdout)
    assert rep["failed"] == 0 and rep["attempted"] > 0
    assert rep["correct"] is (control is None), p.stdout[-3000:]
    assert expects <= set(rep["metrics"]), rep["metrics"]
    for name in ("kernels.mla_moe_decode_hbm_share", "kernels.mla_decode_mxu_share",
                 "kernels.decode_hbm_share", "kernels.hybrid_decode_hbm_share"):
        assert name not in rep["metrics"]
    if trace:
        m = rep["metrics"]
        assert 0 <= m["spec.accepted_share"]["value"] <= 100
        assert 0.5 < m["spec.tokens_per_slot_step"]["value"] <= 2
        assert m["engine.mtp_step_ms_p50"]["value"] > 0
        # at most 4 busy slots x 128 positions x 4 MLA layers x 128 lanes, float32
        # rows priced as bf16: the count is of the configuration's stated type
        assert 0 < m["engine.latent_bytes_per_step"]["value"] <= 4 * 128 * 4 * 128 * 2
    if control:
        assert any("NOT OK" in l for l in p.stdout.splitlines())
    # the module is compared at the prompt's last position (the row that
    # makes a request's first draft) and at the 4 or 5 positions the verify
    # steps touch behind it (the last step's second position comes free)
    answers = [l for l in p.stdout.splitlines() if l.startswith("[correct] answer:")]
    assert answers and all("'mtp_rows_compared': 5" in l or
                           "'mtp_rows_compared': 6" in l for l in answers), answers


def test_without_a_chip_the_new_cell_gives_no_result():
    p = _run(["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert p.returncode not in (0, 10), p.stdout[-2000:]
    assert "needs a TPU" in p.stdout + p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_the_manifest_takes_the_new_entries():
    man = Manifest(ROOT)
    cell = man.cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "longctx-open-loop"
    entry = man.config_entry(CONFIG)
    assert entry["reduced"] == list(REDUCED)
    assert entry["source"] == ("https://huggingface.co/FreedomIntelligence/"
                               "openPangu-Ultra-MoE-718B/blob/main/config.json")
    # no tail: it did not repeat in either hybrid cell, and the list-less
    # per-layer metrics that move it read the dense engine
    e2e = {m["name"] for m in man.metrics_for(CELL, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert {"spec.accepted_share", "spec.tokens_per_slot_step",
            "engine.mtp_step_ms_p50", "moe.pangu_tokens_per_held_expert",
            "engine.latent_bytes_per_step", "kernels.mla_moe_decode_hbm_share",
            "kernels.mla_decode_mxu_share", "engine.batch_occupancy",
            "device.peak_hbm_bytes.serve"} <= per_layer
    assert not {"kernels.decode_hbm_share", "kernels.hybrid_decode_hbm_share",
                "moe.tokens_per_held_expert", "engine.ssm_step_ms_p50"} & per_layer
    for m in per_layer:
        man.find("metrics", m + ".py")
    tr = man.load_traffic(cell["traffic"])
    assert tr["kind"] == "open_loop" and tr["arrival_cv"] == 1.0
    assert tr["warm_s"] == 20
    assert (tr["prompt_tokens"]["min"], tr["prompt_tokens"]["max"]) == (1024, 7168)
    assert (tr["answer_tokens"]["min"], tr["answer_tokens"]["max"]) == (128, 768)
    assert set(tr["limits"]) == {"token_gap_mean_spacings", "prefill_logits_rel_err",
                                 "mtp_logits_rel_err", "route_margin_max"}
    assert set(tr["limits_why"]) == set(tr["limits"])


@pytest.fixture(scope="module")
def c():
    return Manifest(ROOT).load_config(CONFIG)


def test_the_configuration_file_is_the_row_key_by_key(c):
    """Every published key verbatim but the four reduced ones, whose
    published values stand beside them; no width among them."""
    assert {k: c[k] for k in ROW if k not in REDUCED} == \
        {k: v for k, v in ROW.items() if k not in REDUCED}
    for k, v in REDUCED.items():
        assert c[k] == v and c["source_" + k] == ROW[k]
    assert set(c) - set(ROW) == {"torch_dtype", "reference", "experts_held",
                                 "deployment", "assumed", "run"} | \
        {"source_" + k for k in REDUCED}
    assert c["experts_held"] == {"of": 256, "first": 0, "count": 8}
    assert {"deployment", "scoring", "rope", "latent_norms", "mtp_module",
            "biases", "latent_lanes"} <= set(c["assumed"])
    run = c["run"]
    assert (run["num_slots"], run["max_len"], run["prefill_tokens"]) == (32, 8192, 8192)
    assert run["control"] == "int8"
    # the floors: a period and four following layers, 8 experts, an eighth
    assert c["num_hidden_layers"] - c["first_k_dense_replace"] >= 4
    assert c["vocab_size"] * 8 >= ROW["vocab_size"]


def test_the_program_is_configured_from_the_file(c):
    from perfbench.lib import pangu_model

    cfg = pangu_model.model_config(c)
    assert cfg.layer_kinds() == (("mla", "dense"),) + (("mla", "moe"),) * 5
    assert (cfg.n_heads, cfg.q_lora_rank, cfg.kv_lora_rank) == (128, 1536, 512)
    # the stored width is the program's own rule; the counts follow it
    assert cfg.latent_width == pc.latent_lanes(c) == 640
    assert cfg.n_predict == 1 and cfg.sandwich_norm
    assert cfg.experts_held == tuple(range(8)) and cfg.n_experts == 256


# ---- counts against hand arithmetic (the numbers of ISSUE 34) --------------

@pytest.mark.parametrize("fn,want_millions", [
    # 7680x1536 + 1536x(128x192) + 7680x576 + 512x(128x256) + (128x128)x7680
    (pc.mla_mixer_params, 11.80 + 37.75 + 4.42 + 16.78 + 125.83),     # 196.6
    (pc.expert_params, 47.19),
    (pc.expert_layer_fixed_params, 196.58 + 1.97 + 47.19),
    (pc.total_params, 4773.7),         # 621.2 + 5 x 623.2 + 741.2 + 294.9
])
def test_parameter_counts(c, fn, want_millions):
    assert fn(c) / 1e6 == pytest.approx(want_millions, rel=1e-3)


def test_the_count_is_the_programs(c):
    import jax

    from perfbench.lib import pangu_model
    from ray_tpu.models import hybrid

    cfg = pangu_model.model_config(c)
    shapes = jax.eval_shape(lambda k: hybrid.init_params(k, cfg), jax.random.PRNGKey(0))
    matrices = sum(a.size for a in jax.tree_util.tree_leaves(shapes) if a.ndim > 1)
    assert matrices == pc.total_params(c)


def test_step_bytes_and_operations(c):
    assert pc.n_layers_of(c) == (1, 5, 1)
    assert pc.latent_layers(c) == 7 and pc.held_expert_slots(c) == 48
    # a position: 7 layers x 640 stored lanes x 2 B (576 hold [c, k_r])
    assert pc.latent_row_bytes(c) == 7 * 640 * 2
    # 32 slots x 8192 positions: 2.35 GB
    assert pc.latent_bytes_per_step(c, 32 * 8192) / 1e9 == pytest.approx(2.349, abs=0.001)
    # fixed: dense layer 621.2 + 6 x 245.7 + projection 118.0 + head 147.5 = 2361M
    assert pc.decode_fixed_weight_bytes(c) / 1e9 == pytest.approx(
        2 * (621.24 + 6 * 245.74 + 117.96 + 147.46) / 1e3, abs=0.005)
    # every held expert touched, nothing live: all weights but the embedding
    assert pc.decode_step_bytes(c, 0, 48) == \
        2 * (pc.total_params(c) - c["vocab_size"] * c["hidden_size"])
    # 12 busy slots x 4000 rows, 2 positions x 128 heads, scores over the 576
    # values of a row (not its 64 zero lanes) and values over 512: 0.19 TFLOP
    assert pc.mla_decode_flops(c, 48000, 2) / 1e12 == pytest.approx(
        7 * 48000 * 2 * 256 * (576 + 512) / 1e12, rel=1e-9)
    assert pc.mla_decode_flops(c, 48000, 2) / 1e12 == pytest.approx(0.187, abs=0.001)
