"""Cells are data: a throw-away configuration, traffic mix, cell and
per-layer metric, added as files in a temporary directory and run through
the real command on the CPU at tiny size; and the real cells' command path
refuses to print a result without a chip."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
RUN = os.path.join(ROOT, "perfbench", "run.py")

TOY = {"hidden_size": 128, "intermediate_size": 256, "num_hidden_layers": 2,
       "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 512,
       "rope_theta": 1e6, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
       "torch_dtype": "float32", "reference": "dense_decoder",
       "run": {"mesh": {"dp": 1}, "remat": "none", "fused_blocks": False,
               "check_layers": 2, "num_slots": 4, "max_len": 128,
               "max_concurrent_queries": 32}}
STEPS = {"kind": "token_batches", "driver": "train_steps", "batch": 2, "seq": 64,
         "distinct_batches": 2, "warm_steps": 2, "trace_from_step": 2,
         "trace_steps": 3, "check_batch": 2, "check_seq": 64,
         "check_wrt": ["layers.wq", "lm_head"], "control": "float8_e4m3fn",
         "limits": {"fwd_logits_rel_err": 1e-4, "bwd_grad_rel_err": 1e-4,
                    "first_loss_minus_ln_vocab": 1.0, "last_over_first_loss": 1.01}}
CHAT = {"kind": "open_loop", "driver": "open_loop_http", "rate_per_s": 4.0,
        "arrival_cv": 1.0, "warm_s": 1,
        "prompt_tokens": {"log_mean": 2.5, "log_sd": 0.5, "min": 4, "max": 40},
        "answer_tokens": {"log_mean": 1.8, "log_sd": 0.4, "min": 2, "max": 12},
        "slot_rule": {"token_gap_ms": 20, "ttft_ms": 30}, "request_timeout_s": 60,
        "warm": {"prefill_buckets": [8, 16, 32, 64], "admission_batches": [1, 2],
                 "attention_buckets": [64, 128]},
        "trace_window_s": [0.5, 1.5], "check_answers": 3, "control": "int8",
        "limits": {"token_gap_mean_spacings": 0.01, "prefill_logits_rel_err": 1e-4}}


def _throw_away_root(tmp_path):
    """A manifest of its own: the real metrics under toy cell names, plus a
    configuration, two traffic mixes and one metric that exist only here."""
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    rename = {"mistral7b-train-1chip": "toy-train", "internlm2-serve-chat": "toy-serve"}
    extra = tmp_path / "extra"
    for sub in ("configs", "traffic", "metrics"):
        (extra / sub).mkdir(parents=True)
    (extra / "configs" / "toy.json").write_text(json.dumps(TOY))
    (extra / "traffic" / "toy-steps.json").write_text(json.dumps(STEPS))
    (extra / "traffic" / "toy-chat.json").write_text(json.dumps(CHAT))
    (extra / "metrics" / "toy.steps_done.py").write_text(
        'def read(run):\n    return float(run["steps"]) if "steps" in run else None\n')
    metrics = {"end_to_end": [], "per_layer": []}
    for kind in metrics:
        for m in real[kind]:
            m = dict(m)
            if "workloads" in m:
                m["workloads"] = [rename[w] for w in m["workloads"] if w in rename]
                if not m["workloads"]:
                    continue
            metrics[kind].append(m)
    metrics["per_layer"].append({
        "name": "toy.steps_done", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "train_tokens_per_s_per_chip", "workloads": ["toy-train"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps({
        "command": real["command"], "paths": ["extra"], "run_seconds": 2,
        "configs": [{"name": "toy", "source": "none", "file": "extra/configs/toy.json",
                     "reduced": [], "why": "throw-away"}],
        "workloads": [
            {"name": "toy-train", "config": "toy", "traffic": "toy-steps",
             "chips": 1, "why": "throw-away"},
            {"name": "toy-serve", "config": "toy", "traffic": "toy-chat",
             "chips": 1, "why": "throw-away"}],
        **metrics}))
    return str(tmp_path)


def _run(args, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, RUN] + args, capture_output=True,
                          text=True, timeout=timeout, env=env, cwd=ROOT)


def _would_report(stdout):
    line = next(l for l in stdout.splitlines() if "would report: " in l)
    return json.loads(line.split("would report: ", 1)[1])


@pytest.mark.parametrize("cell,trace,expects", [
    ("toy-train", 1, {"toy.steps_done", "trainer.step_ms_p50", "compile.s",
                      "worker.spawn_to_device_s"}),
    ("toy-serve", 0, {"tpot_p95_ms", "serve_tokens_per_s", "setup_s"}),
])
def test_a_cell_added_as_files_runs_through_the_real_command(tmp_path, cell,
                                                             trace, expects):
    p = _run(["--root", _throw_away_root(tmp_path), "--workload", cell,
              "--seed", str(2**31 + 5), "--seconds", "2", "--trace", str(trace),
              "--cpu-rehearsal"])
    assert p.returncode == 10, p.stdout[-2000:] + p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert lines[-1].startswith("[CPU REHEARSAL]")  # never a result line
    rep = _would_report(p.stdout)
    assert rep["correct"] is True and rep["failed"] == 0 and rep["attempted"] > 0
    assert expects <= set(rep["metrics"]), rep["metrics"]
    assert rep["device"]["platform"] == "cpu"
    # a rehearsal has no device trace: no metric that reads one is printed
    assert not {"kernels.train_mxu_share", "device.idle_share.train",
                "kernels.decode_hbm_share"} & set(rep["metrics"])
    assert "[correct]" in p.stdout and "limit" in p.stdout


@pytest.mark.parametrize("cell", ["mistral7b-train-1chip", "internlm2-serve-chat",
                                  "mistral7b-train-4chip"])
def test_without_a_chip_there_is_no_result(cell):
    p = _run(["--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert p.returncode not in (0, 10), p.stdout[-2000:]
    assert "needs a TPU" in p.stdout + p.stderr
    for line in p.stdout.splitlines():
        assert not line.startswith("{"), line  # no result line at all


def test_unknown_workload_is_refused():
    p = _run(["--workload", "nope", "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0 and "no workload" in p.stderr + p.stdout


def test_the_benchmark_alone_is_refused(tmp_path):
    """A directory that holds only BENCHMARK.json and the files under
    `paths` has no system under test: no result, exit code not 0."""
    import shutil

    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in real["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p)
    p = subprocess.run(
        [sys.executable, str(tmp_path / "perfbench" / "run.py"), "--workload",
         real["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=60,
        cwd=tmp_path, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and "no system under test" in p.stderr
    assert not any(l.startswith("{") for l in p.stdout.splitlines())


def test_overrides_lay_values_over_a_traffic_file():
    sys.path.insert(0, ROOT)
    from perfbench.lib.manifest import apply_overrides

    base = {"rate_per_s": 5.4, "warm_s": 8}
    assert apply_overrides(base, ["rate_per_s=6.5"]) == {"rate_per_s": 6.5, "warm_s": 8}
    assert apply_overrides(base, None) == base and base["rate_per_s"] == 5.4


@pytest.mark.parametrize("cell,control,number", [
    ("toy-train", "float8_e4m3fn", "fwd_logits_rel_err"),
    ("toy-serve", "int8", "prefill_logits_rel_err"),
])
def test_the_control_comes_out_as_not_correct(tmp_path, cell, control, number):
    """The reference in the next precision below (train), the engine's own
    int8 weight path (serve), put in the program's place: `correct` is false,
    by the number that is compared with a limit."""
    p = _run(["--root", _throw_away_root(tmp_path), "--workload", cell,
              "--seed", "7", "--seconds", "1", "--trace", "0", "--control", control,
              "--cpu-rehearsal"])
    assert p.returncode == 10, p.stdout[-2000:] + p.stderr[-2000:]
    assert _would_report(p.stdout)["correct"] is False
    assert any(number in l and "NOT OK" in l for l in p.stdout.splitlines())
