"""The hybrid (Kimi-Linear) cell: its toy runs through the real command on
the CPU from a throw-away root; the manifest's new entries; the `counts`
functions against the hand arithmetic of the issue that added the cell."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "perfbench", "run.py")
sys.path.insert(0, ROOT)

from perfbench.lib import hybrid_counts as hc  # noqa: E402
from perfbench.lib.manifest import Manifest  # noqa: E402

CELL = "kimi-linear-serve-longgen"
TOY = {"hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
       "num_hidden_layers": 4, "num_attention_heads": 2, "kv_lora_rank": 32,
       "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
       "num_experts": 4, "num_experts_per_token": 2, "num_shared_experts": 1,
       "first_k_dense_replace": 1, "routed_scaling_factor": 2.446,
       "moe_renormalize": True, "rms_norm_eps": 1e-5, "vocab_size": 512,
       "torch_dtype": "float32", "reference": "kimi_linear",
       "linear_attn_config": {"kda_layers": [1, 2, 4], "full_attn_layers": [3],
                              "head_dim": 16, "num_heads": 2,
                              "short_conv_kernel_size": 4},
       "experts_held": {"of": 8, "first": 4, "count": 4},
       "run": {"num_slots": 4, "max_len": 128, "prefill_tokens": 64,
               "max_concurrent_queries": 32}}
TRAFFIC = {"kind": "open_loop", "driver": "open_loop_http_hybrid",
           "rate_per_s": 4.0, "arrival_cv": 1.0, "warm_s": 1,
           "prompt_tokens": {"log_mean": 2.5, "log_sd": 0.5, "min": 4, "max": 40},
           "answer_tokens": {"log_mean": 1.8, "log_sd": 0.4, "min": 2, "max": 12},
           "slot_rule": {"token_gap_ms": 20, "ttft_ms": 30}, "request_timeout_s": 60,
           "warm": {"prefill_buckets": [8, 16, 32, 64], "admission_batches": [1, 2, 4],
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
    (extra / "configs" / "toy-hybrid.json").write_text(json.dumps(TOY))
    (extra / "traffic" / "toy-reasoning.json").write_text(json.dumps(TRAFFIC))
    metrics = {"end_to_end": [], "per_layer": []}
    for kind in metrics:
        for m in real[kind]:
            m = dict(m)
            if "workloads" in m:
                if CELL not in m["workloads"]:
                    continue
                m["workloads"] = ["toy-hybrid-serve"]
            metrics[kind].append(m)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": real["command"], "paths": ["extra"], "run_seconds": 2,
        "configs": [{"name": "toy-hybrid", "source": "none",
                     "file": "extra/configs/toy-hybrid.json", "reduced": [],
                     "why": "throw-away"}],
        "workloads": [{"name": "toy-hybrid-serve", "config": "toy-hybrid",
                       "traffic": "toy-reasoning", "chips": 1, "why": "throw-away"}],
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
    (1, None, {"moe.experts_touched_share", "moe.tokens_per_held_expert",
               "engine.state_bytes_per_step", "engine.hybrid_step_ms_p50",
               "engine.batch_occupancy"}),
    (0, "int8", set()),
])
def test_the_hybrid_toy_runs_through_the_real_command(tmp_path, trace, control,
                                                      expects):
    """Untraced: the end-to-end metrics; traced: the new counters' metrics
    read numbers (the device-trace one reads nothing on the CPU and is left
    out); the int8 control comes out as not correct."""
    args = ["--root", _throw_away_root(tmp_path), "--workload", "toy-hybrid-serve",
            "--seed", str(2**31 + 11), "--seconds", "2", "--trace", str(trace),
            "--cpu-rehearsal"] + (["--control", control] if control else [])
    p = _run(args)
    assert p.returncode == 10, p.stdout[-3000:] + p.stderr[-3000:]
    rep = _would_report(p.stdout)
    assert rep["failed"] == 0 and rep["attempted"] > 0
    assert rep["correct"] is (control is None), p.stdout[-3000:]
    assert expects <= set(rep["metrics"]), rep["metrics"]
    assert "kernels.hybrid_decode_hbm_share" not in rep["metrics"]
    assert "kernels.decode_hbm_share" not in rep["metrics"]
    if trace:
        m = rep["metrics"]
        assert m["engine.hybrid_step_ms_p50"]["value"] > 0
        # 3 expert layers x 4 held experts of 8; each active slot sends on
        # average 2 x 4/8 = 1 assignment a layer to the held half
        assert 0 < m["moe.experts_touched_share"]["value"] <= 100
        assert 0 < m["moe.tokens_per_held_expert"]["value"] <= 4 * 2 / 4
        assert m["engine.state_bytes_per_step"]["value"] > 0
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
    assert cell["chips"] == 1 and cell["config"] == "kimi-linear-48b-a3b.1of4"
    entry = man.config_entry(cell["config"])
    assert set(entry["reduced"]) == {"num_hidden_layers", "num_experts",
                                     "vocab_size", "linear_attn_config"}
    # the tail of the token gap does not repeat in this cell (PERF.md section
    # 6), so it is no end-to-end metric here, and the per-layer metrics that
    # move it are not read; the step's time comes from the program's own span
    e2e = {m["name"] for m in man.metrics_for(CELL, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert {"moe.experts_touched_share", "moe.tokens_per_held_expert",
            "engine.state_bytes_per_step", "kernels.hybrid_decode_hbm_share",
            "engine.hybrid_step_ms_p50", "engine.batch_occupancy",
            "device.peak_hbm_bytes.serve"} <= per_layer
    # the dense block's count is not read on this model
    assert "kernels.decode_hbm_share" not in per_layer
    assert "kernels.decode_hbm_share" in {
        m["name"] for m in man.metrics_for("internlm2-serve-chat", "per_layer")}
    for m in per_layer:
        man.find("metrics", m + ".py")
    tr = man.load_traffic(cell["traffic"])
    assert tr["prompt_tokens"] == {"log_mean": 6.7, "log_sd": 0.8, "min": 256, "max": 4096}
    assert tr["answer_tokens"] == {"log_mean": 6.0, "log_sd": 0.6, "min": 128, "max": 1024}
    assert tr["warm_s"] == 20 and tr["request_timeout_s"] == 180
    assert tr["arrival_cv"] == 1.0 and tr["kind"] == "open_loop"


def test_the_configuration_keeps_every_published_width():
    c = Manifest(ROOT).load_config("kimi-linear-48b-a3b.1of4")
    published = {"hidden_size": 2304, "intermediate_size": 9216,
                 "moe_intermediate_size": 1024, "num_attention_heads": 32,
                 "kv_lora_rank": 512, "qk_nope_head_dim": 128,
                 "qk_rope_head_dim": 64, "v_head_dim": 128, "head_dim": 72,
                 "num_experts_per_token": 8, "num_shared_experts": 1,
                 "routed_scaling_factor": 2.446, "first_k_dense_replace": 1}
    assert {k: c[k] for k in published} == published
    la = c["linear_attn_config"]
    assert (la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]) == (32, 128, 4)
    # layer 1 dense, then two whole periods: 6 KDA : 2 MLA behind it
    assert la["kda_layers"] == [1, 2, 3, 5, 6, 7, 9] and la["full_attn_layers"] == [4, 8]
    assert c["experts_held"] == {"of": 256, "first": 0, "count": 64}
    assert c["source_num_experts"] == 256 and c["vocab_size"] * 4 == c["source_vocab_size"]
    assert c["source_linear_attn_config"]["kda_layers"][:7] == la["kda_layers"]


def test_what_the_configuration_cuts_is_never_a_width():
    """What `test_perfbench_manifest.py::test_configurations_keep_the_published_
    widths` asks of the two dense configurations, asked of this one here: that
    test has no entry for it and is not this PR's to edit (PERF.md section 7).
    Every reduced number is under its `source_*` value; `vocab_size` is a count
    of rows (this chip's slice), the one `_size` key a share may cut."""
    man = Manifest(ROOT)
    entry = man.config_entry("kimi-linear-48b-a3b.1of4")
    c = man.load_config(entry["name"])
    for key in ("num_hidden_layers", "num_experts", "vocab_size"):
        assert key in entry["reduced"] and c[key] < c["source_" + key]
    assert (c["source_num_hidden_layers"], c["source_vocab_size"]) == (27, 163840)
    width = ("_dim", "_rank", "hidden_size", "intermediate_size", "_heads",
             "per_token")
    assert not any(k.endswith(width) for k in entry["reduced"])
    la, src = c["linear_attn_config"], c["source_linear_attn_config"]
    assert {k: v for k, v in la.items() if not k.endswith("_layers")} == \
        {k: v for k, v in src.items() if not k.endswith("_layers")}


# ---- counts against hand arithmetic (the numbers of ISSUE 28) --------------

@pytest.fixture(scope="module")
def c():
    return Manifest(ROOT).load_config("kimi-linear-48b-a3b.1of4")


@pytest.mark.parametrize("fn,want_millions", [
    (hc.kda_mixer_params, 39.5),                  # q,k,v 28.3 + wo 9.4 + low ranks
    (hc.mla_mixer_params, 29.1),                  # 36.8 less shared 7.08 + router 0.59
    (hc.expert_params, 7.08),
    (lambda c: hc.swiglu_params(c, c["intermediate_size"]), 63.7),
    (hc.param_count, 4273.0),                     # = 8.55 GB in bf16
])
def test_parameter_counts(c, fn, want_millions):
    assert fn(c) / 1e6 == pytest.approx(want_millions, rel=2e-3)


def test_state_and_step_bytes(c):
    assert hc.n_layers_of(c) == (7, 2, 1, 8)
    assert hc.kda_state_bytes_per_slot(c) == 7 * 32 * 128 * 128 * 4     # 14.7 MB
    assert hc.conv_tail_bytes_per_slot(c) == 7 * 3 * 12288 * 2          # 0.5 MB
    assert hc.latent_row_bytes(c) == 2304                               # 2 x 576 x 2 B
    # mixers + shared experts + routers + dense layer 0.92 GB, head 0.19 GB
    assert hc.decode_fixed_weight_bytes(c) / 1e9 == pytest.approx(0.92 + 0.19, abs=0.01)
    # 64 slots: KDA state read and written 1.9 GB
    assert hc.state_bytes_per_step(c, 64, 0) / 1e9 == pytest.approx(1.94, abs=0.02)
    assert hc.state_bytes_per_step(c, 0, 64 * 1000) == 64 * 1000 * 2304
    # 86% of 512 held experts x 14.2 MB = 6.2 GB; the whole step 9.2 GB
    touched = 0.86 * 512
    assert touched * hc.expert_params(c) * 2 / 1e9 == pytest.approx(6.2, abs=0.05)
    assert hc.decode_step_bytes(c, 64, 64 * 1200, touched) / 1e9 == \
        pytest.approx(9.2 + 0.18, abs=0.1)
