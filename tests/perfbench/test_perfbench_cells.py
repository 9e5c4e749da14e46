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


def _throw_away_root(tmp_path, serve="toy-serve", chat=None, run=None):
    """A manifest of its own: the real metrics under toy cell names, plus a
    configuration, two traffic mixes and one metric that exist only here.
    `chat` is laid over the serving traffic file and `run` over the
    configuration's `run` keys; `serve` names the serving cell (its record
    goes to a directory of that name under `.perfbench_out/`)."""
    real = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    rename = {"mistral7b-train-1chip": "toy-train", "internlm2-serve-chat": serve}
    extra = tmp_path / "extra"
    for sub in ("configs", "traffic", "metrics"):
        (extra / sub).mkdir(parents=True)
    (extra / "configs" / "toy.json").write_text(
        json.dumps(dict(TOY, run=dict(TOY["run"], **(run or {})))))
    (extra / "traffic" / "toy-steps.json").write_text(json.dumps(STEPS))
    (extra / "traffic" / "toy-chat.json").write_text(
        json.dumps(dict(CHAT, **(chat or {}))))
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
            {"name": serve, "config": "toy", "traffic": "toy-chat",
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
    ("toy-serve", 0, {"tpot_p50_ms", "serve_tokens_per_s", "setup_s"}),
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
    # each number compared beside its limit: the line's LAST key
    assert list(rep)[-1] == "compared"
    assert {"failed", "other_check_not_ok"} < set(rep["compared"])
    assert all(c["value"] <= c["limit"] for c in rep["compared"].values())


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
    rep = _would_report(p.stdout)
    assert rep["correct"] is False
    assert any(number in l and "NOT OK" in l for l in p.stdout.splitlines())
    assert rep["compared"][number]["value"] > rep["compared"][number]["limit"]


QUEUE, PROXY = "RAY_TPU_SERVE_MAX_QUEUE_PER_REPLICA", "RAY_TPU_SERVE_PROXY_MAX_INFLIGHT"


@pytest.mark.parametrize("config_env,traffic_env,override,said,runs", [
    # a traffic file without the key leaves the configuration's in force: a
    # router whose loaded settings say 0 sheds the driver's first call
    ({QUEUE: "0"}, None, None, "the in-flight cap (0)", False),
    # the traffic file wins, and what it states reaches the router
    ({QUEUE: "0"}, {QUEUE: "64"}, None, f"{QUEUE}=64 (traffic toy-chat)", True),
    # ... and the proxy; the configuration's other setting stays in force
    ({QUEUE: "64"}, {PROXY: "0"}, None, "proxy at in-flight cap (0)", False),
    # `--override` lays it as it lays any key, and marks the line
    ({QUEUE: "0"}, None, {QUEUE: "64"}, f"{QUEUE}=64 (--override)", True),
])
def test_a_traffic_files_serve_env_reaches_the_cluster(tmp_path, config_env,
                                                       traffic_env, override,
                                                       said, runs):
    """The toy serving cell through the real command with `serve_env` stated
    by the configuration, the traffic file or `--override`: the settings the
    router and the proxy LOADED (`ray_tpu/serve/config.py`, read from their
    processes' environment) are the ones the `[traffic]` line says are in
    force. A cap of 0 sheds every request, the driver's own first call too,
    with HTTP 503 and a body that names the cap the process holds."""
    cell = "toy-serve-env"
    root = _throw_away_root(
        tmp_path, serve=cell, run={"serve_env": config_env},
        chat={"serve_env": traffic_env} if traffic_env else None)
    p = _run(["--root", root, "--workload", cell, "--seed", str(2**31 + 52),
              "--seconds", "2", "--trace", "0", "--cpu-rehearsal"]
             + (["--override", "serve_env=" + json.dumps(override)]
                if override else []))
    out = p.stdout + p.stderr
    in_force = next(l for l in p.stdout.splitlines()
                    if l.startswith("[traffic] serve_env in force: "))
    for key, value in config_env.items():
        if key not in (traffic_env or override or {}):
            assert f"{key}={value} (configuration toy)" in in_force
    assert said in out, out[-3000:]
    if not runs:
        assert p.returncode not in (0, 10) and "HTTP 503" in out
        assert "would report" not in p.stdout
        return
    assert p.returncode == 10, out[-3000:]
    rep = _would_report(p.stdout)
    assert rep["correct"] is True and rep["failed"] == 0 and rep["attempted"] > 0
    assert ("not_the_cell" in rep) is bool(override)
    with open(os.path.join(ROOT, ".perfbench_out", cell, "last_run.json")) as f:
        kept = json.load(f)["serve_env"]
    assert kept == {QUEUE: {"value": "64", "from": "--override" if override
                            else "traffic toy-chat"}}


def test_a_serve_env_key_that_is_no_serve_setting_ends_the_run(tmp_path):
    """Before any process of the cluster exists, naming the key and where
    it was stated."""
    root = _throw_away_root(tmp_path, chat={"serve_env": {"JAX_PLATFORMS": "tpu"}})
    p = _run(["--root", root, "--workload", "toy-serve", "--seed", "1",
              "--seconds", "1", "--trace", "0", "--cpu-rehearsal"], timeout=60)
    assert p.returncode not in (0, 10)
    assert "'JAX_PLATFORMS'" in p.stderr and "traffic toy-chat" in p.stderr
    assert "[setup]" not in p.stdout and "would report" not in p.stdout


def test_serve_env_is_laid_configuration_first_traffic_over_it(monkeypatch, capsys):
    sys.path.insert(0, ROOT)
    from perfbench.lib.manifest import lay_serve_env

    drain = "RAY_TPU_SERVE_DRAIN_DEADLINE_S"
    for key in (QUEUE, PROXY, drain):
        monkeypatch.delenv(key, raising=False)
    ctx = {"cell": {"config": "c", "traffic": "t"},
           "config": {"run": {"serve_env": {QUEUE: "128", drain: "5"}}},
           "traffic": {"serve_env": {QUEUE: 2048, PROXY: "4096"}}}
    assert lay_serve_env(ctx) == ctx["serve_env"] == {
        QUEUE: {"value": "2048", "from": "traffic t"},
        drain: {"value": "5", "from": "configuration c"},
        PROXY: {"value": "4096", "from": "traffic t"}}
    assert [os.environ[k] for k in (QUEUE, drain, PROXY)] == ["2048", "5", "4096"]
    assert capsys.readouterr().out.strip() == (
        f"[traffic] serve_env in force: {QUEUE}=2048 (traffic t); {drain}=5 "
        f"(configuration c); {PROXY}=4096 (traffic t)")
    # neither file states any: nothing is set, and the line says so
    assert lay_serve_env({"cell": ctx["cell"], "config": {"run": {}},
                          "traffic": {}}) == {}
    assert capsys.readouterr().out.strip() == "[traffic] serve_env in force: none stated"
