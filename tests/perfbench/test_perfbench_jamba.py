"""The Jamba cell: its toy runs through the real command on the CPU from a
throw-away root; the manifest's new entries; the configuration file against
the catalog row it was drawn from; the `jamba_counts` functions against the
hand arithmetic of the issue that added the cell."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "perfbench", "run.py")
sys.path.insert(0, ROOT)

from perfbench.lib import jamba_counts as jc  # noqa: E402
from perfbench.lib.manifest import Manifest  # noqa: E402

CELL = "jamba2-serve-chat-burst"
# the catalog row's `config` (model-configs guide, architectures.jsonl,
# AI21-Jamba2-3B), copied here so that the test needs no file outside the repo
ROW = {"attn_layer_offset": 7, "attn_layer_period": 14, "expert_layer_offset": 1,
       "expert_layer_period": 2, "hidden_act": "silu", "hidden_size": 2560,
       "intermediate_size": 8192, "mamba_conv_bias": True, "mamba_d_conv": 4,
       "mamba_d_state": 16, "mamba_dt_rank": 160, "mamba_expand": 2,
       "mamba_proj_bias": False, "max_position_embeddings": 262144,
       "model_type": "jamba", "num_attention_heads": 20, "num_experts": 1,
       "num_experts_per_tok": 1, "num_hidden_layers": 28, "num_key_value_heads": 1,
       "num_logits_to_keep": 1, "rms_norm_eps": 1e-06, "sliding_window": None,
       "tie_word_embeddings": True, "use_mamba_kernels": True, "vocab_size": 65536}
TOY = {"attn_layer_offset": 2, "attn_layer_period": 4, "hidden_size": 64,
       "intermediate_size": 128, "mamba_conv_bias": True, "mamba_d_conv": 4,
       "mamba_d_state": 16, "mamba_dt_rank": 8, "mamba_expand": 2,
       "mamba_proj_bias": False, "num_attention_heads": 4, "num_experts": 1,
       "num_hidden_layers": 8, "num_key_value_heads": 1, "rms_norm_eps": 1e-6,
       "tie_word_embeddings": True, "vocab_size": 512, "torch_dtype": "float32",
       "reference": "jamba",
       "run": {"num_slots": 4, "max_len": 128, "prefill_tokens": 128,
               "max_concurrent_queries": 32}}
TRAFFIC = {"kind": "open_loop", "driver": "open_loop_http_jamba",
           "rate_per_s": 4.0, "arrival_cv": 2.0, "warm_s": 1,
           "prompt_tokens": {"log_mean": 2.5, "log_sd": 0.5, "min": 4, "max": 40},
           "answer_tokens": {"log_mean": 1.8, "log_sd": 0.4, "min": 2, "max": 12},
           "slot_rule": {"token_gap_ms": 20, "ttft_ms": 30}, "request_timeout_s": 60,
           "warm": {"prefill_buckets": [8, 16, 32, 64], "admission_batches": [1, 2, 4],
                    "attention_buckets": [64, 128]},
           "trace_window_s": [0.5, 1.5], "check_answers": 3,
           "check_decode_steps": 2, "control": "int8",
           "limits": {"token_gap_mean_spacings": 0.01,
                      "prefill_logits_rel_err": 1e-4}}


def _throw_away_root(tmp_path):
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    extra = tmp_path / "extra"
    for sub in ("configs", "traffic"):
        (extra / sub).mkdir(parents=True)
    (extra / "configs" / "toy-jamba.json").write_text(json.dumps(TOY))
    (extra / "traffic" / "toy-burst.json").write_text(json.dumps(TRAFFIC))
    metrics = {"end_to_end": [], "per_layer": []}
    for kind in metrics:
        for m in real[kind]:
            m = dict(m)
            if "workloads" in m:
                if CELL not in m["workloads"]:
                    continue
                m["workloads"] = ["toy-jamba-serve"]
            metrics[kind].append(m)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": real["command"], "paths": ["extra"], "run_seconds": 2,
        "configs": [{"name": "toy-jamba", "source": "none",
                     "file": "extra/configs/toy-jamba.json", "reduced": [],
                     "why": "throw-away"}],
        "workloads": [{"name": "toy-jamba-serve", "config": "toy-jamba",
                       "traffic": "toy-burst", "chips": 1, "why": "throw-away"}],
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
    (1, None, {"engine.ssm_step_ms_p50", "engine.ssm_state_bytes_per_step",
               "engine.batch_occupancy", "compile.s"}),
    (0, "int8", set()),
])
def test_the_jamba_toy_runs_through_the_real_command(tmp_path, trace, control,
                                                     expects):
    """Untraced: the end-to-end metrics; traced: the new counters' metrics
    read numbers (the two device-trace ones read nothing on the CPU and are
    left out); the int8 control comes out as not correct. Exit 10."""
    args = ["--root", _throw_away_root(tmp_path), "--workload", "toy-jamba-serve",
            "--seed", str(2**31 + 11), "--seconds", "2", "--trace", str(trace),
            "--cpu-rehearsal"] + (["--control", control] if control else [])
    p = _run(args)
    assert p.returncode == 10, p.stdout[-3000:] + p.stderr[-3000:]
    rep = _would_report(p.stdout)
    assert rep["failed"] == 0 and rep["attempted"] > 0
    assert rep["correct"] is (control is None), p.stdout[-3000:]
    assert expects <= set(rep["metrics"]), rep["metrics"]
    for name in ("kernels.ssm_decode_hbm_share", "kernels.ssm_scan_hbm_share",
                 "kernels.ssm_step_hbm_share", "kernels.decode_hbm_share",
                 "kernels.hybrid_decode_hbm_share"):
        assert name not in rep["metrics"]
    if trace:
        m = rep["metrics"]
        assert m["engine.ssm_step_ms_p50"]["value"] > 0
        # at most 4 busy slots x 6 Mamba layers' state and tail, read + written
        per_slot = 6 * (16 * 128 * 4 + 3 * 128 * 4)
        assert 0 < m["engine.ssm_state_bytes_per_step"]["value"] <= \
            2 * 4 * per_slot + 4 * 128 * 2 * 2 * 16 * 4
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
    assert cell["chips"] == 1 and cell["config"] == "jamba2-3b"
    entry = man.config_entry(cell["config"])
    assert entry["reduced"] == []
    assert entry["source"] == \
        "https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json"
    # the tail of the token gap does not repeat over six seeds in this cell
    # (11% at the arrival_cv kept; the traffic file's `rate_why`), so it is no
    # end-to-end metric here and the per-layer metrics that move it are not read
    e2e = {m["name"] for m in man.metrics_for(CELL, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert {"engine.ssm_step_ms_p50", "engine.ssm_state_bytes_per_step",
            "kernels.ssm_decode_hbm_share", "kernels.ssm_scan_hbm_share",
            "kernels.ssm_step_hbm_share", "engine.batch_occupancy",
            "device.peak_hbm_bytes.serve"} <= per_layer
    # the other models' counts are not read on this one
    assert not {"kernels.decode_hbm_share", "kernels.hybrid_decode_hbm_share",
                "moe.experts_touched_share"} & per_layer
    assert "kernels.decode_hbm_share" in {
        m["name"] for m in man.metrics_for("internlm2-serve-chat", "per_layer")}
    for m in per_layer:
        man.find("metrics", m + ".py")
    tr = man.load_traffic(cell["traffic"])
    chat = man.load_traffic("chat-open-loop")
    # the chat cell's own length mix: the two models are read under one shape
    assert tr["prompt_tokens"] == chat["prompt_tokens"]
    assert tr["answer_tokens"] == chat["answer_tokens"]
    assert tr["warm_s"] == 12 and tr["kind"] == "open_loop"
    assert tr["arrival_cv"] == 1.5 and tr["rate_per_s"] == 22   # 0.8 x the knee of 28
    assert set(tr["limits"]) == {"token_gap_mean_spacings", "prefill_logits_rel_err"}
    assert set(tr["limits_why"]) == set(tr["limits"])


@pytest.fixture(scope="module")
def c():
    return Manifest(ROOT).load_config("jamba2-3b")


def test_the_configuration_file_is_the_row_key_by_key(c):
    assert {k: c[k] for k in ROW} == ROW
    assert set(c) - set(ROW) == {"torch_dtype", "reference", "deployment",
                                 "assumed", "run"}
    assert {"inner_norms", "A_log", "dt_bias", "D", "conv_bias"} <= set(c["assumed"])
    assert c["run"]["max_len"] == 1024 and c["run"]["max_concurrent_queries"] == 1024
    assert c["run"]["serve_env"] == {"RAY_TPU_SERVE_MAX_QUEUE_PER_REPLICA": "1024"}


def test_the_layer_pattern_is_thirteen_to_one(c):
    from perfbench.lib.manifest import load_py
    ref = load_py(os.path.join(ROOT, "perfbench", "references", "jamba.py"))
    kinds = ref.layer_kinds(c)
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [7, 21]
    assert ref.runs(c) == [("mamba", 7), ("attn", 1), ("mamba", 13), ("attn", 1),
                           ("mamba", 6)]
    assert jc.n_layers_of(c) == (26, 2)


# ---- counts against hand arithmetic (the numbers of ISSUE 32) --------------

@pytest.mark.parametrize("fn,want_millions", [
    (jc.mamba_mixer_params, 41.24),    # W_in 26.21, W_out 13.11, W_x 0.98, W_dt 0.82
    (jc.attn_mixer_params, 13.76),
    (jc.swiglu_params, 62.91),
    (jc.param_count, 3029.0),          # 26 x 104.2 + 2 x 76.7 + 167.8 = 6.06 GB bf16
])
def test_parameter_counts(c, fn, want_millions):
    assert fn(c) / 1e6 == pytest.approx(want_millions, rel=1e-3)


def test_state_and_step_bytes(c):
    assert jc.ssm_state_bytes_per_slot(c) == 26 * 327680
    assert jc.conv_tail_bytes_per_slot(c) == 26 * 30720
    assert jc.kv_row_bytes(c) == 2 * 512
    # 256 slots: 2.39 GB of recurrent state, read and written 4.77 GB a step
    assert jc.state_bytes_per_step(c, 256, 0) / 1e9 == pytest.approx(2 * 2.386, abs=0.01)
    assert jc.state_bytes_per_step(c, 0, 256 * 1024) / 1e9 == pytest.approx(0.268, abs=0.001)
    assert jc.decode_weight_bytes(c) / 1e9 == pytest.approx(6.06, abs=0.01)
    assert jc.decode_step_bytes(c, 256, 0) == \
        jc.decode_weight_bytes(c) + jc.state_bytes_per_step(c, 256, 0)
    # one token: 2 x 3.03e9 multiply-adds through stack and head
    assert jc.decode_flops(c) * 256 / 1e12 == pytest.approx(1.55, abs=0.01)
    # one call of the step kernel at 64 busy slots: 64 x 655 KB of state both ways
    assert jc.step_kernel_bytes(c, 64) / 1e6 == pytest.approx(
        64 * (0.65536 + 0.06144 + 0.000128) + 0.328, abs=0.01)
    assert jc.step_kernel_bytes(c, 0) == 16 * 5120 * 4
    # one call of the scan kernel on 4 x 1024 positions: u, dt, y 3 x 84 MB
    assert jc.scan_kernel_bytes(c, 4, 1024) / 1e6 == pytest.approx(
        3 * 83.9 + 0.52 + 2.62 + 0.33, abs=0.5)
