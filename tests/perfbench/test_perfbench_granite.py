"""The Granite cell: its toy runs through the real command on the CPU from a
throw-away root; the manifest's new entries; the configuration file against
the catalog row it was drawn from; the `granite_counts` functions against the
hand arithmetic of the issue that added the cell; the new metric readers on
another cell's record."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "perfbench", "run.py")
sys.path.insert(0, ROOT)

from perfbench.lib import granite_counts as gc  # noqa: E402
from perfbench.lib.manifest import Manifest  # noqa: E402

CELL = "granite4h-serve-ragsessions"
NEW_METRICS = {"engine.ssd_step_ms_p50", "engine.ssd_state_bytes_per_step",
               "moe.ssd_tokens_per_held_expert", "moe.ssd_experts_touched_share",
               "kernels.ssd_moe_decode_hbm_share", "kernels.ssd_step_hbm_share"}
# the catalog row's `config` (model-configs guide, architectures.jsonl,
# granite-4.0-h-small), copied here so that the test needs no file outside
# the repo
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
ROW = {"attention_bias": False, "attention_multiplier": 0.0078125,
       "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 4096,
       "intermediate_size": 768, "layer_types": PERIOD * 4, "logits_scaling": 16,
       "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
       "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
       "mamba_n_groups": 1, "mamba_n_heads": 128, "mamba_proj_bias": False,
       "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
       "normalization_function": "rmsnorm", "num_attention_heads": 32,
       "num_experts_per_tok": 10, "num_hidden_layers": 40,
       "num_key_value_heads": 8, "num_local_experts": 72,
       "position_embedding_type": "nope", "residual_multiplier": 0.22,
       "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
       "shared_intermediate_size": 1536, "tie_word_embeddings": True,
       "vocab_size": 100352}
REDUCED = {"num_hidden_layers": 10, "num_local_experts": 36, "vocab_size": 50176}
TOY = {"hidden_size": 64, "intermediate_size": 32, "shared_intermediate_size": 64,
       "layer_types": ["mamba", "mamba", "mamba", "attention"] * 2,
       "num_hidden_layers": 8, "mamba_n_heads": 8, "mamba_d_head": 16,
       "mamba_d_state": 16, "mamba_n_groups": 1, "mamba_d_conv": 4,
       "mamba_expand": 2, "mamba_chunk_size": 16, "mamba_conv_bias": True,
       "mamba_proj_bias": False, "num_attention_heads": 4,
       "num_key_value_heads": 2, "position_embedding_type": "nope",
       "num_local_experts": 4, "num_experts_per_tok": 3,
       "experts_held": {"of": 8, "first": 0, "count": 4},
       "embedding_multiplier": 3, "attention_multiplier": 0.125,
       "residual_multiplier": 0.5, "logits_scaling": 4, "rms_norm_eps": 1e-5,
       "tie_word_embeddings": True, "vocab_size": 512, "torch_dtype": "float32",
       "reference": "granite_moe_hybrid",
       "run": {"num_slots": 4, "max_len": 128, "prefill_tokens": 64,
               "max_concurrent_queries": 32}}
TRAFFIC = {"kind": "open_loop", "driver": "open_loop_http_granite",
           "rate_per_s": 4.0, "arrival_cv": 1.0, "warm_s": 1,
           "prompt_tokens": {"log_mean": 2.8, "log_sd": 0.5, "min": 4, "max": 60},
           "answer_tokens": {"log_mean": 1.8, "log_sd": 0.4, "min": 2, "max": 12},
           "slot_rule": {"token_gap_ms": 20, "ttft_ms": 30}, "request_timeout_s": 60,
           "warm": {"prefill_buckets": [8, 16, 32, 64], "admission_batches": [1],
                    "attention_buckets": [64, 128]},
           "trace_window_s": [0.5, 1.5], "check_answers": 3,
           "check_decode_steps": 2, "control": "int8",
           "limits": {"token_gap_mean_spacings": 0.01,
                      "prefill_logits_rel_err": 1e-4, "route_margin_max": 1e-4}}


def _throw_away_root(tmp_path):
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    extra = tmp_path / "extra"
    for sub in ("configs", "traffic"):
        (extra / sub).mkdir(parents=True)
    (extra / "configs" / "toy-granite.json").write_text(json.dumps(TOY))
    (extra / "traffic" / "toy-rag.json").write_text(json.dumps(TRAFFIC))
    metrics = {"end_to_end": [], "per_layer": []}
    for kind in metrics:
        for m in real[kind]:
            m = dict(m)
            if "workloads" in m:
                if CELL not in m["workloads"]:
                    continue
                m["workloads"] = ["toy-granite-serve"]
            metrics[kind].append(m)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": real["command"], "paths": ["extra"], "run_seconds": 2,
        "configs": [{"name": "toy-granite", "source": "none",
                     "file": "extra/configs/toy-granite.json", "reduced": [],
                     "why": "throw-away"}],
        "workloads": [{"name": "toy-granite-serve", "config": "toy-granite",
                       "traffic": "toy-rag", "chips": 1, "why": "throw-away"}],
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
    (1, None, {"engine.ssd_step_ms_p50", "engine.ssd_state_bytes_per_step",
               "moe.ssd_tokens_per_held_expert", "moe.ssd_experts_touched_share",
               "engine.batch_occupancy", "compile.s"}),
    (0, "int8", set()),
])
def test_the_granite_toy_runs_through_the_real_command(tmp_path, trace, control,
                                                       expects):
    """Untraced: the end-to-end metrics; traced: the new counters' metrics
    read numbers (the two device-trace ones read nothing on the CPU and
    are left out); the int8 control comes out as not correct. Exit 10."""
    args = ["--root", _throw_away_root(tmp_path), "--workload", "toy-granite-serve",
            "--seed", str(2**31 + 11), "--seconds", "2", "--trace", str(trace),
            "--cpu-rehearsal"] + (["--control", control] if control else [])
    p = _run(args)
    assert p.returncode == 10, p.stdout[-3000:] + p.stderr[-3000:]
    rep = _would_report(p.stdout)
    assert rep["failed"] == 0 and rep["attempted"] > 0
    assert rep["correct"] is (control is None), p.stdout[-3000:]
    assert expects <= set(rep["metrics"]), rep["metrics"]
    for name in ("kernels.ssd_moe_decode_hbm_share", "kernels.ssd_step_hbm_share",
                 "kernels.ssm_decode_hbm_share",
                 "kernels.hybrid_decode_hbm_share", "engine.ssm_step_ms_p50"):
        assert name not in rep["metrics"]
    if trace:
        m = rep["metrics"]
        assert m["engine.ssd_step_ms_p50"]["value"] > 0
        # at most 4 busy slots x 6 Mamba-2 layers' state and tail, read + written
        per_slot = 6 * (16 * 128 * 4 + 3 * 160 * 4)
        assert 0 < m["engine.ssd_state_bytes_per_step"]["value"] <= 2 * 4 * per_slot
        # 3 of 8 experts a token, 4 held: 1.5 land a layer and busy slot
        assert 0 < m["moe.ssd_tokens_per_held_expert"]["value"] <= 4 * 3 / 4
        assert 0 < m["moe.ssd_experts_touched_share"]["value"] <= 100
    if control:
        assert any("NOT OK" in l for l in p.stdout.splitlines())


def test_without_a_chip_the_new_cell_gives_no_result():
    p = _run(["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert p.returncode not in (0, 10), p.stdout[-2000:]
    assert "needs a TPU" in p.stdout + p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_the_manifest_takes_the_new_entries():
    man = Manifest(ROOT)
    cell = man.cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == "granite-4.0-h-small.1of2"
    assert cell["traffic"] == "rag-sessions-open-loop" and len(cell["why"]) <= 200
    entry = man.config_entry(cell["config"])
    assert entry["reduced"] == ["num_hidden_layers", "num_local_experts", "vocab_size"]
    assert entry["source"] == \
        "https://huggingface.co/ibm-granite/granite-4.0-h-small/blob/main/config.json"
    e2e = {m["name"] for m in man.metrics_for(CELL, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert NEW_METRICS | {"engine.batch_occupancy",
                          "device.peak_hbm_bytes.serve"} <= per_layer
    # the other models' counts are not read here; PR 36's token-path metrics
    # are, since PR 52 (`test_perfbench_token_path.py` reads their lists)
    assert not {"kernels.decode_hbm_share", "kernels.hybrid_decode_hbm_share",
                "moe.experts_touched_share", "kernels.ssm_step_hbm_share",
                "kernels.ssd_scan_hbm_share"} & per_layer
    assert {"engine.driver_device_wait_share", "engine.wakes_per_token"} <= per_layer
    for name in NEW_METRICS:
        m = next(m for m in man.data["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
        man.find("metrics", name + ".py")
    tr = man.load_traffic(cell["traffic"])
    assert tr["kind"] == "open_loop" and tr["arrival_cv"] == 1.0 and tr["warm_s"] == 20
    assert tr["prompt_tokens"] == {"log_mean": 8.19, "log_sd": 0.6, "min": 1024,
                                   "max": 12288}
    assert tr["answer_tokens"] == {"log_mean": 5.55, "log_sd": 0.5, "min": 96,
                                   "max": 768}
    conf = man.load_config(cell["config"])
    assert tr["prompt_tokens"]["max"] + tr["answer_tokens"]["max"] \
        < conf["run"]["max_len"]
    assert set(tr["limits"]) == {"token_gap_mean_spacings", "prefill_logits_rel_err",
                                 "route_margin_max"}
    assert set(tr["limits_why"]) == set(tr["limits"])


@pytest.fixture(scope="module")
def c():
    return Manifest(ROOT).load_config("granite-4.0-h-small.1of2")


def test_the_configuration_file_is_the_row_but_for_the_cut(c):
    """Every width is the catalog row's; the three reduced keys carry the
    published value beside them; no width is among them."""
    for key, value in ROW.items():
        if key in REDUCED:
            assert c[key] == REDUCED[key] and c["source_" + key] == value
        else:
            assert c[key] == value, key
    assert set(c) - set(ROW) == {
        "torch_dtype", "reference", "source_num_hidden_layers",
        "source_num_local_experts", "source_vocab_size", "experts_held",
        "deployment", "arithmetic", "assumed", "run"}
    assert c["experts_held"] == {"of": 72, "first": 0, "count": 36}
    assert c["layer_types"][:c["num_hidden_layers"]] == PERIOD
    assert {"router", "expert_halves", "in_projection_split", "gated_norm", "A_log",
            "dt_bias", "D", "conv_bias", "expert_width", "dtypes",
            "deployment"} <= set(c["assumed"])
    assert 24 <= c["run"]["num_slots"] <= 32 and c["run"]["max_len"] == 16384
    # the floors: a whole period, 8 routed experts, an eighth of the vocabulary
    assert c["num_local_experts"] >= 8 and 8 * c["vocab_size"] >= ROW["vocab_size"]


def test_the_file_makes_the_program_configuration(c):
    from perfbench.lib import granite_model

    cfg = granite_model.model_config(c)
    assert cfg.runs() == (("mamba2", 5), ("attn", 1), ("mamba2", 4))
    assert cfg.run_ffns() == ("moe", "moe", "moe")
    assert (cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_state, cfg.ssd_chunk) == \
        (128, 64, 128, 256)
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 128)
    assert (cfg.n_experts, len(cfg.experts_held), cfg.top_k, cfg.d_expert,
            cfg.d_expert * cfg.n_shared) == (72, 36, 10, 768, 1536)
    assert (cfg.embed_scale, cfg.attn_scale, cfg.residual_scale,
            cfg.logit_divisor) == (12.0, 1 / 128, 0.22, 16.0)
    assert cfg.router == "softmax" and cfg.vocab_size == 50176
    cache_rows = gc.slot_bytes(c, c["run"]["max_len"])
    assert cache_rows / 1e6 == pytest.approx(105.3, abs=0.1)


# ---- counts against hand arithmetic (the numbers of ISSUE 49) --------------

@pytest.mark.parametrize("fn,want_millions", [
    (gc.mamba2_mixer_params, 102.29),  # W_in 68.68, W_out 33.55, conv 0.042, 0.0086
    (gc.attn_mixer_params, 41.94),
    (gc.shared_params, 18.87),
    (gc.router_params, 0.295),
    (gc.expert_params, 9.437),
    (lambda c: gc.layer_params(c, "mamba"), 461.2),
    (lambda c: gc.layer_params(c, "attention"), 400.9),
    (gc.param_count, 4757.1),          # 9 x 461.2 + 400.9 + 205.5 = 9.51 GB bf16
])
def test_parameter_counts(c, fn, want_millions):
    assert fn(c) / 1e6 == pytest.approx(want_millions, rel=1e-3)


def test_state_and_step_bytes(c):
    assert gc.n_layers_of(c) == (9, 1) and gc.held_expert_slots(c) == 360
    assert gc.ssm_state_bytes_per_slot(c) == 9 * 128 * 64 * 128 * 4     # 37.7 MB
    assert gc.conv_tail_bytes_per_slot(c) == 9 * 3 * 8448 * 2           # 0.46 MB
    assert gc.kv_row_bytes(c) == 4096
    assert gc.state_bytes_per_step(c, 10) == 2 * 10 * (37748736 + 456192)
    assert gc.decode_fixed_weight_bytes(c) / 1e9 == pytest.approx(2.72, abs=0.01)
    # the issue's step at 10 busy slots: 80% of the held experts touched
    assert gc.decode_step_bytes(c, 10, 40000, 288) / 1e9 == pytest.approx(9.08, abs=0.02)
    assert gc.decode_step_bytes(c, 0, 0, 0) == gc.decode_fixed_weight_bytes(c)
    assert gc.step_kernel_bytes(c, 1) == 4 * (2 * 128 * 8192 + 3 * 8192 + 256)


def test_the_new_readers_read_nothing_on_another_cells_record():
    """A record of another model's cell (steps with `latent_rows`, or with
    `kv_rows` but no expert counters; no `ssd_*` kernel calls; no `tokens` on
    the prompt passes): every new reader returns None and does not raise."""
    from perfbench.lib.manifest import load_py

    step = lambda **args: {"name": "engine.step", "ph": "X", "ts": 1e6 * 100.5,
                           "dur": 9000.0, "pid": 1, "tid": 1, "args": args}
    events = [step(state_slots=3, kv_rows=700, active=3),
              step(state_slots=3, latent_rows=900, expert_assignments=40,
                   experts_touched=30, active=3),
              {"name": "engine.prefill_dispatch", "ph": "X", "ts": 1e6 * 100.6,
               "dur": 100.0, "pid": 1, "tid": 1, "args": {"bucket": 64, "batch": 1}}]
    run = {"rows": [], "window_rows": [], "t_open": 100.0, "seconds": 2.0,
           "config": Manifest(ROOT).load_config("granite-4.0-h-small.1of2"),
           "traffic": {"trace_window_s": [0.0, 2.0]},
           "device": {"kind": "TPU v5e"},
           "_program_window": {"traces": [], "steps": events[:2]},
           "program_spans": {"events": events, "info": {}},
           "trace": {"module_ms_p50": {"jit_decode_step": 9.0},
                     "kernel_calls": {"selective_scan": [[1, 128, 0.001]],
                                      "selective_step": [4, 0.002]}}}
    for name in sorted(NEW_METRICS):
        read = load_py(os.path.join(ROOT, "perfbench", "metrics", name + ".py")).read
        assert read(run) is None, name
