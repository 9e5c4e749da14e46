"""The EvaByte cell: its toy runs through the real command on the CPU from a
throw-away root and prints every new metric; the manifest's new entries; the
configuration file against the catalog row it was drawn from; the
`eva_counts` functions against the hand arithmetic of the issue that added
the cell and against a count of the seeded tree."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "perfbench", "run.py")
sys.path.insert(0, ROOT)

from perfbench.lib import eva_counts as ec  # noqa: E402
from perfbench.lib.manifest import Manifest, load_py  # noqa: E402

CELL = "evabyte-serve-longdoc"
# the catalog row's `config` (model-configs guide, architectures.jsonl,
# EvaByte), copied here so that the test needs no file outside the repo
ROW = {"attention_bias": False, "attention_class": "eva", "chunk_size": 16,
       "fp32_ln": False, "fp32_logits": True, "fp32_skip_add": True,
       "hidden_act": "silu", "hidden_size": 4096, "init_cutoff_factor": None,
       "init_fn": "v2", "init_std": 0.01275, "intermediate_size": 11008,
       "lazy_init": True, "max_position_embeddings": 32768, "max_seq_length": 32768,
       "mixedp_attn": True, "model_type": "evabyte", "norm_add_unit_offset": True,
       "num_attention_heads": 32, "num_chunks": None, "num_hidden_layers": 32,
       "num_key_value_heads": 32, "num_pred_heads": 8, "rms_norm_eps": 1e-05,
       "rope_scaling": None, "rope_theta": 100000, "tie_word_embeddings": False,
       "vocab_size": 320, "window_size": 2048}
NEW_METRICS = {"engine.eva_step_ms_p50", "engine.eva_cache_bytes_per_step",
               "eva.summary_rows_share", "eva.chunks_closed_per_step",
               "kernels.eva_decode_step_hbm_share", "kernels.eva_attend_hbm_share"}
TOY = {**ROW, "chunk_size": 4, "window_size": 32, "hidden_size": 64,
       "intermediate_size": 128, "num_attention_heads": 4, "num_key_value_heads": 4,
       "num_hidden_layers": 3, "num_pred_heads": 2, "vocab_size": 64,
       "torch_dtype": "float32", "reference": "evabyte",
       "run": {"num_slots": 4, "max_len": 256, "prefill_tokens": 32,
               "max_concurrent_queries": 32}}
TRAFFIC = {"kind": "open_loop", "driver": "open_loop_http_eva",
           "rate_per_s": 3.0, "arrival_cv": 1.0, "warm_s": 1,
           "prompt_tokens": {"log_mean": 4.2, "log_sd": 0.4, "min": 40, "max": 120},
           "answer_tokens": {"log_mean": 2.8, "log_sd": 0.4, "min": 8, "max": 40},
           "slot_rule": {"token_gap_ms": 20, "ttft_ms": 30}, "request_timeout_s": 60,
           "warm": {"prefill_buckets": [64, 96, 128], "admission_batches": [1],
                    "attention_buckets": [64, 128, 256]},
           "trace_window_s": [0.5, 1.5], "check_answers": 3,
           "check_decode_steps": 8, "control": "int8",
           "limits": {"token_gap_mean_spacings": 0.01,
                      "prefill_logits_rel_err": 1e-4, "decode_logits_rel_err": 1e-4}}


def _throw_away_root(tmp_path):
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    extra = tmp_path / "extra"
    for sub in ("configs", "traffic"):
        (extra / sub).mkdir(parents=True)
    (extra / "configs" / "toy-eva.json").write_text(json.dumps(TOY))
    (extra / "traffic" / "toy-files.json").write_text(json.dumps(TRAFFIC))
    metrics = {"end_to_end": [], "per_layer": []}
    for kind in metrics:
        for m in real[kind]:
            m = dict(m)
            if "workloads" in m:
                if CELL not in m["workloads"]:
                    continue
                m["workloads"] = ["toy-eva-serve"]
            metrics[kind].append(m)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": real["command"], "paths": ["extra"], "run_seconds": 2,
        "configs": [{"name": "toy-eva", "source": "none",
                     "file": "extra/configs/toy-eva.json", "reduced": [],
                     "why": "throw-away"}],
        "workloads": [{"name": "toy-eva-serve", "config": "toy-eva",
                       "traffic": "toy-files", "chips": 1, "why": "throw-away"}],
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
    (1, None, {"engine.eva_step_ms_p50", "engine.eva_cache_bytes_per_step",
               "eva.summary_rows_share", "eva.chunks_closed_per_step",
               "engine.batch_occupancy", "compile.s", "worker.spawn_to_device_s"}),
    (0, "int8", set()),
])
def test_the_eva_toy_runs_through_the_real_command(tmp_path, trace, control, expects):
    """Untraced: the end-to-end metrics; traced: every new metric that reads
    the program's counters and spans, with a value (the two that read the
    device's trace find none on the CPU and are left out); the int8 control
    fails the prompt passes' and the decode positions' limits, each. Exit 10."""
    args = ["--root", _throw_away_root(tmp_path), "--workload", "toy-eva-serve",
            "--seed", str(2**31 + 11), "--seconds", "2", "--trace", str(trace),
            "--cpu-rehearsal"] + (["--control", control] if control else [])
    p = _run(args)
    assert p.returncode == 10, p.stdout[-3000:] + p.stderr[-3000:]
    rep = _would_report(p.stdout)
    assert rep["failed"] == 0 and rep["attempted"] > 0
    assert rep["correct"] is (control is None), p.stdout[-3000:]
    assert expects <= set(rep["metrics"]), rep["metrics"]
    for name in ("kernels.eva_decode_step_hbm_share", "kernels.eva_attend_hbm_share",
                 "kernels.ssm_decode_hbm_share", "kernels.decode_hbm_share"):
        assert name not in rep["metrics"]
    closes = next(l for l in p.stdout.splitlines() if "compared decode positions" in l)
    assert closes.endswith("ok") and "NOT" not in closes
    if trace:
        m = rep["metrics"]
        assert m["engine.eva_step_ms_p50"]["value"] > 0
        # at most 4 busy slots x (31 window rows + 7 windows' 8 summaries), K
        # and V of 4 heads x 16 x float32... priced as bf16 by the counts
        assert 0 < m["engine.eva_cache_bytes_per_step"]["value"] <= \
            3 * 4 * (31 + 56) * 2 * 4 * 16 * 2
        assert 0 < m["eva.summary_rows_share"]["value"] < 100
        assert 0 < m["eva.chunks_closed_per_step"]["value"] <= 4
    if control:
        bad = [l for l in p.stdout.splitlines() if "NOT OK" in l]
        assert any("prefill_logits_rel_err" in l for l in bad)
        assert any("decode_logits_rel_err" in l for l in bad)


def test_without_a_chip_the_new_cell_gives_no_result():
    p = _run(["--workload", CELL, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert p.returncode not in (0, 10), p.stdout[-2000:]
    assert "needs a TPU" in p.stdout + p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_the_manifest_takes_the_new_entries():
    man = Manifest(ROOT)
    cell = man.cell(CELL)
    assert cell["chips"] == 1 and cell["config"] == "evabyte-6.5b.1of4"
    assert len(cell["why"]) <= 200
    entry = man.config_entry(cell["config"])
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == \
        "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
    e2e = {m["name"] for m in man.metrics_for(CELL, "end_to_end")}
    assert e2e == {"serve_tokens_per_s", "setup_s"}
    per_layer = {m["name"] for m in man.metrics_for(CELL, "per_layer")}
    assert NEW_METRICS <= per_layer
    # the list-less readers that move what this cell reports, and (PR 52)
    # the twelve of the token's way out, which list every serving cell
    token_path = load_py(os.path.join(ROOT, "tests", "perfbench",
                                      "test_perfbench_token_path.py"))
    assert per_layer == NEW_METRICS | set(token_path.NAMES) | {
        "engine.batch_occupancy", "device.peak_hbm_bytes.serve", "compile.s",
        "worker.spawn_to_device_s"}
    # the other models' counts are not read on this one, nor this one's on them
    assert not {"kernels.decode_hbm_share", "kernels.ssm_decode_hbm_share",
                "moe.experts_touched_share", "spec.accepted_share"} & per_layer
    for other in ("jamba2-serve-chat-burst", "internlm2-serve-chat"):
        assert not NEW_METRICS & {m["name"] for m in man.metrics_for(other, "per_layer")}
    for m in per_layer:
        man.find("metrics", m + ".py")
    for m in man.data["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
    tr = man.load_traffic(cell["traffic"])
    assert tr["kind"] == "open_loop" and tr["driver"] == "open_loop_http_eva"
    assert tr["arrival_cv"] == 1.0 and tr["warm_s"] == 20
    assert tr["prompt_tokens"] == {"log_mean": 9.23, "log_sd": 0.55,
                                   "min": 4096, "max": 28672}
    assert tr["answer_tokens"] == {"log_mean": 6.24, "log_sd": 0.5,
                                   "min": 192, "max": 1536}
    assert tr["check_decode_steps"] >= 40 and tr["control"] == "int8"
    assert set(tr["limits"]) == {"token_gap_mean_spacings", "prefill_logits_rel_err",
                                 "decode_logits_rel_err"}
    assert set(tr["limits_why"]) == set(tr["limits"])
    c = man.load_config(cell["config"])
    assert tr["prompt_tokens"]["max"] + tr["answer_tokens"]["max"] < c["run"]["max_len"]


@pytest.fixture(scope="module")
def c():
    return Manifest(ROOT).load_config("evabyte-6.5b.1of4")


def test_the_configuration_file_is_the_row_but_for_depth(c):
    """Every published width as published; `reduced` names depth only."""
    assert {k: c[k] for k in ROW if k != "num_hidden_layers"} == \
        {k: v for k, v in ROW.items() if k != "num_hidden_layers"}
    assert c["num_hidden_layers"] == 8 and c["source_num_hidden_layers"] == 32
    assert set(c) - set(ROW) == {"torch_dtype", "reference", "deployment", "assumed",
                                 "run", "source_num_hidden_layers"}
    assert {"summary_form", "windows", "rope", "head_layout", "phi_mu_init",
            "deployment"} <= set(c["assumed"])
    assert "four pipeline stages" in c["deployment"]
    assert c["run"]["max_len"] == 32768 and 12 <= c["run"]["num_slots"] <= 16
    assert c["run"]["control"] == "int8"


def test_the_file_makes_the_program_configuration(c):
    from perfbench.lib import eva_model

    cfg = eva_model.model_config(c)
    assert cfg.runs() == (("eva", 8),)
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff) == \
        (4096, 32, 32, 128, 11008)
    assert (cfg.eva_window, cfg.eva_chunk, cfg.n_pred_heads, cfg.vocab_size) == \
        (2048, 16, 8, 320)
    assert cfg.rope_theta == 1e5 and cfg.norm_unit_offset and cfg.norm_eps == 1e-5
    with pytest.raises(ValueError, match="EVA attention"):
        eva_model.model_config({**c, "attention_class": "softmax"})


# ---- counts against hand arithmetic (the numbers of ISSUE 39) --------------

@pytest.mark.parametrize("fn,want_millions", [
    (ec.mixer_params, 67.11),        # 4 x 4096^2
    (ec.swiglu_params, 135.27),      # 3 x 4096 x 11008
    (ec.head_params, 10.49),         # 4096 x 8 x 320
    (ec.param_count, 1630.8),        # 8 x 202.38 + 1.31 + 10.49 = 3.26 GB bf16
])
def test_parameter_counts(c, fn, want_millions):
    assert fn(c) / 1e6 == pytest.approx(want_millions, rel=1e-3)


def test_the_whole_model_is_six_and_a_half_billion(c):
    assert ec.param_count({**c, "num_hidden_layers": 32}) / 1e9 == \
        pytest.approx(6.488, abs=0.001)


def test_cache_and_step_bytes(c):
    assert ec.row_bytes(c) == 16384
    assert ec.rows_per_slot(c, 32768) == 4096          # plain attention: 32768
    # 16 slots: 8.59 GB of tables + 16 rows a slot and layer of open chunk
    assert ec.cache_bytes(c, 16, 32768) == 8 * 16 * 16384 * (4096 + 16)
    assert ec.cache_bytes(c, 16, 32768) / 1e9 == pytest.approx(8.62, abs=0.01)
    # a step reads the layers and ONE head's 320 columns, not the eight heads'
    assert ec.decode_weight_bytes(c) == 2 * (8 * 202375168 + 4096 * 320)
    # 8 busy slots at the median: ~1000 window rows + 640 summaries each
    assert ec.cache_bytes_per_step(c, 8 * 1000, 8 * 640) / 1e9 == \
        pytest.approx(1.72, abs=0.01)
    assert ec.decode_step_bytes(c, 8000, 5120) == \
        ec.decode_weight_bytes(c) + ec.cache_bytes_per_step(c, 8000, 5120)
    assert ec.attend_kernel_bytes(c, 8000, 5120) * 8 == \
        ec.cache_bytes_per_step(c, 8000, 5120)
    # a 10,240-byte prompt: 33.2 TFLOP of products + 1.7 of attention
    assert ec.prompt_pass_flops(c, 10240) / 1e12 == pytest.approx(34.9, abs=0.1)
    assert ec.decode_flops(c) == 2 * (8 * 202375168 + 4096 * 320)


def test_the_counts_are_the_seeded_tree(c):
    """`param_count` against the leaves `init_params` makes (shapes alone):
    every matrix, without the norms and the summary vectors."""
    import jax

    from perfbench.lib import eva_model
    from ray_tpu.models import hybrid

    cfg = eva_model.model_config(c)
    tree = jax.eval_shape(lambda k: hybrid.init_params(k, cfg), jax.random.PRNGKey(0))
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    small = {"mixer_norm", "ffn_norm", "final_norm", "phi", "mu"}
    matrices = sum(a.size for path, a in leaves if path[-1].key not in small)
    assert matrices == ec.param_count(c)
    rest = sum(a.size for path, a in leaves if path[-1].key in small)
    assert rest == 17 * 4096 + 2 * 8 * 32 * 128
    state = jax.eval_shape(lambda: cfg.make_cache(16, 32768).state)
    assert sum(a.size * a.dtype.itemsize for a in jax.tree_util.tree_leaves(state)) \
        == ec.cache_bytes(c, 16, 32768)
