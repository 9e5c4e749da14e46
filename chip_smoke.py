"""chip_smoke.py — the quickest proof that ray_tpu still starts on the chip.

    python chip_smoke.py              # one chip: train phase, then serve phase
    python chip_smoke.py --chips 4    # four chips: sharded train vs one device,
                                      # then four one-chip replicas
    python chip_smoke.py --model pangu  # one chip: the openPangu-Ultra-MoE cut,
                                        # prompt pass + verify steps + module
    python chip_smoke.py --model jamba  # one chip, one minute: the Jamba stack
                                      # (scanned runs of Mamba and attention
                                      # layers) against its float32 reference

Drives the main path once through the entry points a user calls:
`ray_tpu.init` -> `JaxTrainer` / `serve.run` + HTTP proxy -> a worker that was
spawned for its chip grant -> a jitted JAX program on the TPU, with model b1
(1.14B) at full width and depth, weights made from `--seed`.

One process per chip: THIS process never imports jax. Each phase runs in its
own worker, which holds the chip and has exited before the next one starts.
Any phase that raises, any device that is not a known TPU, any kernel missing
from the compiled step, any wrong answer -> non-zero exit and no result line.
The last line of stdout is the device line and nothing more.

`--cpu-rehearsal` runs the same control flow on the CPU with a tiny model and
interpret-mode kernels, to find wrong paths before spending chip time. It says
so on every line and never prints `"ok": true`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import ray_tpu
from ray_tpu.core.chips import default_compile_cache_dir
from ray_tpu.serve.llm import LLMReplica

TAG = ""  # "[CPU REHEARSAL] " on every line of a rehearsal
KNOWN_DEVICE_KINDS = ("TPU v5 lite", "TPU v5e")  # what jax calls a v5e chip


def say(phase: str, **fields) -> None:
    body = " ".join(f"{k}={json.dumps(v) if not isinstance(v, str) else v}"
                    for k, v in fields.items())
    print(f"{TAG}[{phase}] {body}", flush=True)


def require(cond, msg: str) -> None:
    if not cond:
        raise SystemExit(f"{TAG}chip_smoke FAILED: {msg}")


# --------------------------------------------------------------------------
# code that runs in the worker that holds the chip (jax is imported THERE)


class _CompileCounter:
    """Counts jax's own compile and persistent-cache events in this process."""

    def __init__(self):
        from jax import monitoring

        self.n = {"lowerings": 0, "cache_hits": 0, "cache_misses": 0}
        monitoring.register_event_listener(self._event)
        monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, name, **_):
        if name.endswith("/compilation_cache/cache_hits"):
            self.n["cache_hits"] += 1
        elif name.endswith("/compilation_cache/cache_misses"):
            self.n["cache_misses"] += 1

    def _duration(self, name, _secs, **_):
        if name.endswith("/compile/jaxpr_to_mlir_module_duration"):
            self.n["lowerings"] += 1

    def snapshot(self):
        return dict(self.n)


def _device_report():
    import jax

    d = jax.devices()
    return {"pid": os.getpid(), "tpu_ids": ray_tpu.get_tpu_ids(),
            "platform": d[0].platform, "device_kind": d[0].device_kind,
            "device_count": len(d),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
            "jax_platforms": os.environ.get("JAX_PLATFORMS"),
            "compile_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")}


def _require_chip(rep: dict, rehearsal: bool) -> None:
    """Fail in the worker, before any work, if it was not given a TPU."""
    if not rehearsal and rep["platform"] != "tpu":
        raise RuntimeError(
            f"chip_smoke needs a TPU; this worker (JAX_PLATFORMS="
            f"{rep['jax_platforms']}) found platform={rep['platform']!r} "
            f"({rep['device_kind']})")


def train_loop(config):
    """The train worker: flash-kernel check, then b1 steps. Reports one dict."""
    import dataclasses
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.air import session
    from ray_tpu.models import ModelConfig, count_params
    from ray_tpu.ops.attention import attention, causal_attention_reference
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.train import batch_sharding, make_train_step
    from ray_tpu.train.step import default_optimizer

    counter = _CompileCounter()
    rehearsal = config["rehearsal"]
    out = dict(_device_report())
    _require_chip(out, rehearsal)
    dev = jax.devices()
    if rehearsal:  # the CPU backend shows every virtual device to everyone
        dev = dev[:config["chips"]]
        out["device_count"] = len(dev)

    # Does block_until_ready block here? (perfbench times around it.)
    n = 256 if rehearsal else 8192
    x = jnp.ones((n, n), jnp.bfloat16)
    f = jax.jit(lambda a: (a @ a) * 1e-4)
    f(x).block_until_ready()
    t0 = time.perf_counter()
    y = x
    for _ in range(20):
        y = f(y)
    t_enq = time.perf_counter() - t0
    y.block_until_ready()
    t_bur = time.perf_counter() - t0
    float(jax.device_get(y[0, 0]))
    t_get = time.perf_counter() - t0
    out["sync"] = {"enqueue_s": t_enq, "after_block_until_ready_s": t_bur,
                   "after_device_get_s": t_get}

    # Flash kernel against the plain reference at [64, 2048, 128] bf16.
    b, h, s, d = (1, 2, 128, 128) if rehearsal else (4, 16, 2048, 128)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(config["seed"]), 3)
    q, k, v = (jax.random.normal(key, (b, h, s, d), jnp.bfloat16)
               for key in (kq, kk, kv))
    got = attention(q, k, v).astype(jnp.float32)
    want = causal_attention_reference(q, k, v).astype(jnp.float32)
    out["flash"] = {"shape": [b * h, s, d],
                    "max_abs_err": float(jnp.max(jnp.abs(got - want))),
                    "finite": bool(jnp.all(jnp.isfinite(got)))}
    del q, k, v, got, want

    if rehearsal:
        cfg = dataclasses.replace(ModelConfig.tiny(), max_seq_len=128)
        seq = 128
    else:
        cfg = dataclasses.replace(
            ModelConfig.b1(), max_seq_len=2048, remat="dots", loss_chunk=0,
            fused_ffn=config["fused"], fused_attn=config["fused"])
        seq = 2048
    out["fused_blocks"] = config["fused"]
    mesh = make_mesh(MeshConfig(**config["mesh"]), dev)
    step_fn, init_fn, _ = make_train_step(cfg, mesh, default_optimizer())
    state = init_fn(jax.random.PRNGKey(config["seed"]))
    out["n_params"] = count_params(state.params)
    out["model"] = {k: getattr(cfg, k) for k in (
        "vocab_size", "d_model", "n_layers", "n_heads", "n_kv_heads", "d_ff")}
    out["batch"] = [config["batch"], seq]
    out["mesh"] = {k: int(v) for k, v in mesh.shape.items()}

    # every parameter leaf: on which devices, and what share of its bytes
    # on each (norm vectors are replicated by design: share 1.0, few bytes)
    leaves, device_bytes, total = [], {}, 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(state.params)[0]:
        nbytes = leaf.size * leaf.dtype.itemsize
        total += nbytes
        per_dev = {}
        for sh in leaf.addressable_shards:
            b = sh.data.size * sh.data.dtype.itemsize
            per_dev[sh.device.id] = per_dev.get(sh.device.id, 0) + b
            device_bytes[sh.device.id] = device_bytes.get(sh.device.id, 0) + b
        leaves.append({"leaf": jax.tree_util.keystr(path), "bytes": nbytes,
                       "devices": len(per_dev),
                       "max_share": max(per_dev.values()) / nbytes})
    out["param_shards"] = {
        "leaves": len(leaves),
        "min_devices": min(r["devices"] for r in leaves),
        "device_share_of_param_bytes": {
            str(d): round(b / total, 4) for d, b in sorted(device_bytes.items())},
        "replicated_leaves": [r["leaf"] for r in leaves if r["max_share"] == 1.0
                              and r["devices"] > 1],
        "largest_leaf": max(leaves, key=lambda r: r["bytes"])}

    rng = np.random.default_rng(config["seed"])
    tokens = rng.integers(0, cfg.vocab_size, (config["batch"], seq + 1))
    b_sh = batch_sharding(mesh)
    batch = {"inputs": jax.device_put(jnp.asarray(tokens[:, :-1], jnp.int32), b_sh["inputs"]),
             "targets": jax.device_put(jnp.asarray(tokens[:, 1:], jnp.int32), b_sh["targets"])}

    before = counter.snapshot()
    t0 = time.perf_counter()
    compiled = step_fn.lower(state, batch).compile()
    out["compile_s"] = time.perf_counter() - t0
    after = counter.snapshot()
    out["compile_cache"] = {k: after[k] - before[k]
                            for k in ("cache_hits", "cache_misses")}
    hlo = compiled.as_text()
    out["tpu_custom_calls"] = hlo.count('custom_call_target="tpu_custom_call"')
    out["collectives"] = {op: hlo.count(f" {op}(") for op in (
        "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
        "collective-permute-start")}
    ma = compiled.memory_analysis()
    out["memory_analysis"] = {"argument_bytes": ma.argument_size_in_bytes,
                              "temp_bytes": ma.temp_size_in_bytes}

    losses, step_s = [], []
    for _ in range(config["steps"]):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        jax.block_until_ready(metrics)
        step_s.append(time.perf_counter() - t0)
        losses.append(float(metrics["loss"]))
    out["losses"] = losses
    out["step_s"] = step_s
    out["expected_first_loss"] = math.log(cfg.vocab_size)
    stats = dev[0].memory_stats() or {}
    out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    out["bytes_limit"] = stats.get("bytes_limit")
    session.report(out)


class SmokeLLM(LLMReplica):
    """The serve replica: b1 weights from the seed, built here, plus the
    checks that need those weights (the driver never holds them)."""

    def __init__(self, *args, rehearsal: bool = False, **kwargs):
        _require_chip(_device_report(), rehearsal)
        self._counter = _CompileCounter()
        self._rehearsal = rehearsal
        super().__init__(*args, **kwargs)
        self._ref = None

    def __call__(self, payload):
        return {"tokens": super().__call__(payload), "pid": os.getpid(),
                "tpu_ids": ray_tpu.get_tpu_ids()}

    def info(self, _payload=None):
        import jax

        from ray_tpu.models import count_params

        stats = jax.devices()[0].memory_stats() or {}
        rep = _device_report()
        if self._rehearsal:  # the CPU backend shows every virtual device
            rep["device_count"] = 1
        return {**rep, "compiles": self._counter.snapshot(),
                "n_params": count_params(self.params),
                "dtype": str(self.cfg.dtype.__name__),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use")}

    def check(self, payload):
        """Compare an engine answer with the reference on the same weights,
        at every one of its tokens. `inference.generate` says how far the
        two agree outright. Then the reference is run teacher-forced — the
        same prefill + decode steps, fed the ENGINE's tokens — and reports,
        per step, its top logit and its logit of the token the engine chose:
        after a tie the engine may take the other branch, and every later
        token is still held to the reference given that prefix."""
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import inference

        prompt, answer = list(payload["prompt"]), list(payload["answer"])
        n, max_len = len(answer), self.engine.max_len
        if self._ref is None:
            def forced(params, prompt, answer, cfg, max_len):
                logits, cache = inference.prefill(params, prompt, cfg, max_len)

                def step(carry, tok):
                    cache, logits = carry
                    row = logits[0]
                    seen = (jnp.max(row), row[tok], jnp.argmax(row))
                    logits, cache = inference.decode_step(
                        params, cache, tok[None], cfg)
                    return (cache, logits), seen

                return jax.lax.scan(step, (cache, logits), answer)[1]

            self._ref = jax.jit(forced, static_argnames=("cfg", "max_len"))
        ids = jnp.asarray([prompt], jnp.int32)
        ref = inference.generate(self.params, ids, self.cfg,
                                 max_new_tokens=n, max_len=max_len)
        ref = [int(t) for t in jax.device_get(ref)[0, len(prompt):]]
        top, got, arg = jax.device_get(self._ref(
            self.params, ids, jnp.asarray(answer, jnp.int32), self.cfg, max_len))
        return {"prompt_len": len(prompt), "new_tokens": n,
                "agree_tokens": next((i for i, (a, r) in enumerate(
                    zip(answer, ref)) if a != r), n),
                "off_argmax": [
                    {"position": i, "engine": answer[i], "reference": int(arg[i]),
                     "reference_top_logit": float(top[i]),
                     "gap": float(top[i] - got[i])}
                    for i in range(n) if answer[i] != int(arg[i])]}

    def decode_probe(self, payload):
        """Steady-state decode step time: a second engine over the SAME
        weights and shapes (so the same compiled programs), all slots busy,
        stepped by hand with the host clock around each step."""
        from ray_tpu.models.serving import ContinuousBatchingEngine

        eng = ContinuousBatchingEngine(
            self.params, self.cfg, num_slots=self.engine.num_slots,
            max_len=self.engine.max_len)
        for i in range(eng.num_slots):
            eng.submit([1 + i] * 16, max_new_tokens=int(payload["new_tokens"]))
        before = self._counter.snapshot()
        times = []
        while True:
            t0 = time.perf_counter()
            left = eng.step()
            times.append(time.perf_counter() - t0)
            if left == 0:
                break
        after = self._counter.snapshot()
        steady = sorted(times[4:-2])  # past admission, before the tail
        return {"steps": len(times), "slots": eng.num_slots,
                "decode_step_ms_median": 1e3 * steady[len(steady) // 2],
                "decode_step_ms_min": 1e3 * steady[0],
                "lowerings_during_probe": after["lowerings"] - before["lowerings"]}


# --------------------------------------------------------------------------
# the parent: never imports jax


HTTP_TIMEOUT_S = 600  # first requests wait for compilation


def _post(url: str, payload):
    sep = "&" if "?" in url else "?"
    try:
        return urllib.request.urlopen(urllib.request.Request(
            f"{url}{sep}timeout_s={HTTP_TIMEOUT_S}",
            data=json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"}),
            timeout=HTTP_TIMEOUT_S + 30)
    except urllib.error.HTTPError as e:  # say what the server said, then fail
        raise SystemExit(f"{TAG}chip_smoke FAILED: POST {url} -> HTTP "
                         f"{e.code}: {e.read().decode(errors='replace')[:2000]}")


def http_json(url: str, payload):
    with _post(url, payload) as r:
        return json.loads(r.read())["result"]


def http_stream(url: str, payload):
    """POST to the streaming route; returns the items, one ndjson line each
    (a failure inside the stream arrives as an item with an `error` key)."""
    with _post(url, payload) as r:
        items = [json.loads(line) for line in r if line.strip()]
    bad = [i for i in items if isinstance(i, dict) and "error" in i]
    require(not bad, f"the stream carried an error: {bad}")
    return items


def wait_gone(pid: int, what: str, timeout: float = 120.0) -> float:
    """The chip stays with a process until it has exited: wait for that."""
    t0 = time.monotonic()
    while os.path.exists(f"/proc/{pid}"):
        require(time.monotonic() - t0 < timeout,
                f"{what} pid {pid} still alive {timeout}s after its phase ended")
        time.sleep(0.1)
    return time.monotonic() - t0


def check_device(rep: dict, phase: str, chips: int, rehearsal: bool) -> None:
    require(rep["pid"] != os.getpid(), f"{phase} ran in the parent process")
    require(len(rep["tpu_ids"]) == chips,
            f"{phase} worker got tpu_ids={rep['tpu_ids']}, wanted {chips} chips")
    require(rep["device_count"] == chips,
            f"{phase} worker sees {rep['device_count']} devices, not {chips}")
    if rehearsal:
        return
    require(rep["platform"] == "tpu",
            f"{phase} worker computed on platform={rep['platform']!r}, not tpu")
    require(rep["device_kind"] in KNOWN_DEVICE_KINDS,
            f"{phase}: unknown device_kind {rep['device_kind']!r}")


def run_train(args, chips: int, mesh: dict, label: str,
              fused: bool = True) -> dict:
    from ray_tpu.air import ScalingConfig
    from ray_tpu.train import JaxTrainer

    trainer = JaxTrainer(
        train_loop,
        train_loop_config={"seed": args.seed, "batch": TRAIN_BATCH,
                           "steps": TRAIN_STEPS, "mesh": mesh, "chips": chips,
                           "fused": fused, "rehearsal": args.cpu_rehearsal},
        scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                     chips_per_worker=chips))
    t0 = time.monotonic()
    result = trainer.fit()
    if result.error is not None:
        raise SystemExit(f"{TAG}chip_smoke FAILED: {label} phase: {result.error}")
    rep = result.metrics
    check_device(rep, label, chips, args.cpu_rehearsal)
    say(label, pid=rep["pid"], parent_pid=os.getpid(), tpu_ids=rep["tpu_ids"],
        platform=rep["platform"], device_kind=rep["device_kind"],
        device_count=rep["device_count"], visible_chips=rep["visible_chips"],
        jax_platforms=rep["jax_platforms"], phase_s=round(time.monotonic() - t0, 1))
    say(label, model=rep["model"], n_params=rep["n_params"], batch=rep["batch"],
        mesh=rep["mesh"], fused_blocks=rep["fused_blocks"])
    say(label, sync=rep["sync"])
    say(label, flash_vs_reference=rep["flash"])
    say(label, losses=rep["losses"], expected_first=rep["expected_first_loss"])
    say(label, step_ms=[round(1e3 * s, 2) for s in rep["step_s"]],
        compile_s=round(rep["compile_s"], 2), compile_cache=rep["compile_cache"],
        compile_cache_dir=rep["compile_cache_dir"])
    say(label, tpu_custom_calls=rep["tpu_custom_calls"],
        collectives=rep["collectives"], memory_analysis=rep["memory_analysis"],
        peak_bytes_in_use=rep["peak_bytes_in_use"], bytes_limit=rep["bytes_limit"])
    say(label, param_shards=rep["param_shards"])

    losses = rep["losses"]
    require(len(losses) >= 3 and all(l == l and abs(l) < 1e9 for l in losses),
            f"{label} losses not finite: {losses}")
    require(abs(losses[0] - rep["expected_first_loss"]) < 0.5,
            f"{label} first loss {losses[0]} is not ~ln(vocab)")
    require(losses[-1] < losses[0], f"{label} loss did not fall: {losses}")
    require(rep["flash"]["finite"] and rep["flash"]["max_abs_err"] < 2e-2,
            f"flash kernel disagrees with the reference: {rep['flash']}")
    if not args.cpu_rehearsal:
        sync = rep["sync"]
        require(sync["after_block_until_ready_s"] > 5 * sync["enqueue_s"],
                f"block_until_ready did not block: {sync}")
        require(rep["tpu_custom_calls"] >= 4,
                f"{label}: {rep['tpu_custom_calls']} tpu_custom_call in the "
                "compiled step, want >= 4 (flash fwd, dq, dkv, FFN K3)")
    gone_s = wait_gone(rep["pid"], f"{label} worker")
    say(label, worker_exited_after_s=round(gone_s, 2))
    return rep


TRAIN_BATCH, TRAIN_STEPS = 2, 5  # b1 at 2 sequences of 2048; it fits
PROMPT_LENS = (16, 40, 97, 150, 223, 300)
NEW_TOKENS = 32
TIE_MARGIN = 1e-2  # logit gap under which the engine may take the other token
# How far under the reference's top logit the engine's token may lie, in bf16
# spacings of that logit: one token, and the mean over a round's tokens. The
# engine and the reference are two bf16 executions of the same arithmetic
# (8 slots against 1, bucketed prefill, on the TPU a Pallas attention kernel
# against einsums), so they round differently. Measured over 768 tokens of
# these prompt lengths on four seeds (chip runs, PR 29): mean 0.026 / 0.025,
# largest 2.0 / 2.0, over one spacing 4 / 3 tokens (kernel / einsum path; the
# same against a float32 reference: 0.026 / 0.020, 2.0 / 2.6). A wrong cache
# row or mask puts tokens tens of spacings under; weights rounded to int8 read
# a mean of 0.35 (PERF.md, the serving cell's control).
NEAR_TIE_SPACINGS, MEAN_GAP_SPACINGS = 4.0, 0.12


def bf16_spacing(x: float) -> float:
    """Distance between neighbouring bf16 values at |x| (8 bits of mantissa)."""
    import math

    return 2.0 ** (math.floor(math.log2(abs(x))) - 7)


def make_prompts(seed: int, vocab: int, lens):
    import random

    rnd = random.Random(seed)
    return [[rnd.randrange(1, vocab) for _ in range(n)] for n in lens]


def deploy(args, num_replicas: int):
    from ray_tpu import serve

    if args.cpu_rehearsal:
        model = dict(preset="tiny", num_slots=8, max_len=512)
        vocab = 512
    else:
        model = dict(preset="b1", num_slots=8, max_len=512)  # bf16 weights
        vocab = 32768
    D = serve.deployment(
        SmokeLLM, name="LLM", num_replicas=num_replicas,
        ray_actor_options={"resources": {"TPU": 1}, "num_cpus": 0})
    serve.run(D.bind(seed=args.seed, rehearsal=args.cpu_rehearsal, **model))
    _, port = serve.start_http_proxy()
    return f"http://127.0.0.1:{port}/LLM", vocab


def serve_round(base: str, prompts, tag: str):
    """All requests at once (>= 4 in flight), the last through the
    streaming route. Returns the answers in prompt order."""
    inflight = {"now": 0, "max": 0}
    lock = threading.Lock()

    def one(i):
        payload = {"prompt": prompts[i], "max_new_tokens": NEW_TOKENS}
        with lock:
            inflight["now"] += 1
            inflight["max"] = max(inflight["max"], inflight["now"])
        try:
            if i == len(prompts) - 1:
                return http_stream(base + "/stream?stream=1", payload)
            toks = http_json(base, payload)["tokens"]  # prompt + new tokens
            require(toks[:len(prompts[i])] == prompts[i],
                    f"request {i}: the answer does not start with its prompt")
            return toks[len(prompts[i]):]
        finally:
            with lock:
                inflight["now"] -= 1

    t0 = time.monotonic()
    with ThreadPoolExecutor(len(prompts)) as pool:
        # reading every result re-raises what a request raised
        answers = [f.result() for f in [pool.submit(one, i)
                                        for i in range(len(prompts))]]
    say("serve", round=tag, requests=len(prompts), max_in_flight=inflight["max"],
        streamed=1, wall_s=round(time.monotonic() - t0, 2))
    return answers


def run_serve(args) -> dict:
    from ray_tpu import serve

    t0 = time.monotonic()
    base, vocab = deploy(args, num_replicas=1)
    info = http_json(base + "/info", {})
    check_device(info, "serve", 1, args.cpu_rehearsal)
    say("serve", pid=info["pid"], parent_pid=os.getpid(), tpu_ids=info["tpu_ids"],
        platform=info["platform"], device_kind=info["device_kind"],
        device_count=info["device_count"], visible_chips=info["visible_chips"],
        n_params=info["n_params"], dtype=info["dtype"],
        deploy_s=round(time.monotonic() - t0, 1))

    prompts = make_prompts(args.seed, vocab, PROMPT_LENS)
    serve_round(base, prompts, "warm-up")
    warm = http_json(base + "/info", {})["compiles"]
    answers = serve_round(base, prompts, "measured")
    after = http_json(base + "/info", {})["compiles"]
    say("serve", lowerings_after_warmup=after["lowerings"] - warm["lowerings"],
        compile_cache_hits=after["cache_hits"],
        compile_cache_misses=after["cache_misses"],
        compile_cache_dir=info["compile_cache_dir"])

    gaps = []  # of the tokens off the reference's choice, in bf16 spacings
    for prompt, answer in zip(prompts, answers):
        require(len(answer) == NEW_TOKENS,
                f"a request got {len(answer)} tokens, wanted {NEW_TOKENS}")
        res = http_json(base + "/check", {"prompt": prompt, "answer": answer})
        for off in res["off_argmax"]:
            off["bf16_spacing"] = bf16_spacing(off["reference_top_logit"])
        say("serve", vs_generate=res)
        for off in res["off_argmax"]:
            require(off["position"] > 0,
                    f"first token differs from inference.generate: {res}")
            # the engine's token must be the reference's best too, up to a
            # near-tie between two bf16 executions (NEAR_TIE_SPACINGS)
            require(off["gap"] <= max(TIE_MARGIN,
                                      NEAR_TIE_SPACINGS * off["bf16_spacing"]),
                    f"token {off['position']} is not the reference's choice "
                    f"given the same prefix, nor tied with it: {res}")
            gaps.append(off["gap"] / off["bf16_spacing"])
    mean_gap = sum(gaps) / (len(prompts) * NEW_TOKENS)
    say("serve", token_gap_mean_spacings=round(mean_gap, 4),
        token_gap_max_spacings=round(max(gaps, default=0.0), 3))
    require(mean_gap <= MEAN_GAP_SPACINGS,
            f"the engine's tokens lie {mean_gap:.3f} bf16 spacings under the "
            f"reference's on average, over {MEAN_GAP_SPACINGS}")
    probe = http_json(base + "/decode_probe", {"new_tokens": 48})
    say("serve", decode_probe=probe)
    final = http_json(base + "/info", {})
    say("serve", peak_bytes_in_use=final["peak_bytes_in_use"])
    serve.shutdown()
    gone_s = wait_gone(info["pid"], "serve replica")
    say("serve", worker_exited_after_s=round(gone_s, 2))
    return info


def run_four_chips(args) -> dict:
    """Only what exists across chips, and what it is compared with."""
    from ray_tpu import serve

    # The fused FFN/attention blocks are Mosaic kernels inside custom_vjps
    # and exist for one chip only; on a mesh the step runs the plain block
    # with the flash kernel under shard_map. Both sides of the comparison
    # run that same configuration.
    sharded = run_train(args, 4, {"dp": 1, "fsdp": 2, "tp": 2}, "train-4chip",
                        fused=False)
    single = run_train(args, 1, {"dp": 1}, "train-1chip", fused=False)
    diffs = [abs(a - b) for a, b in zip(sharded["losses"], single["losses"])]
    say("train-4chip", loss_abs_diff_vs_1chip=diffs)
    require(max(diffs) < 5e-2, f"sharded losses leave the one-device losses: {diffs}")
    ps = sharded["param_shards"]
    shares = ps["device_share_of_param_bytes"]
    require(ps["min_devices"] == 4 and len(shares) == 4
            and max(shares.values()) < 0.3
            and ps["largest_leaf"]["max_share"] < 0.3,
            f"parameters are not spread over four chips: {ps}")

    base, vocab = deploy(args, num_replicas=4)
    prompts = make_prompts(args.seed, vocab, (16,) * 16)
    seen = {}
    for _ in range(4):  # the router spreads load; a few rounds reach all four
        with ThreadPoolExecutor(len(prompts)) as pool:
            got = list(pool.map(lambda p: http_json(
                base, {"prompt": p, "max_new_tokens": 4}), prompts))
        for g in got:
            require(len(g["tokens"]) == 16 + 4, f"replica answered {g}")
            seen[g["pid"]] = tuple(g["tpu_ids"])
        if len(seen) == 4:
            break
    say("serve-4x1", replicas_that_answered=len(seen),
        pid_to_chip={str(k): list(v) for k, v in seen.items()})
    require(len(seen) == 4, f"only {len(seen)} of 4 replicas answered")
    require(len(set(seen.values())) == 4 and all(len(v) == 1 for v in seen.values()),
            f"replicas do not hold four different chips: {seen}")
    # every replica's own view of its device, asked until all four have told
    infos = {}
    for _ in range(64):
        i = http_json(base + "/info", {})
        infos[i["pid"]] = i
        if len(infos) == 4:
            break
    require(set(infos) == set(seen), f"info reached {sorted(infos)} of {sorted(seen)}")
    for i in infos.values():
        check_device(i, "serve-4x1", 1, args.cpu_rehearsal)
        say("serve-4x1", pid=i["pid"], tpu_ids=i["tpu_ids"],
            visible_chips=i["visible_chips"], platform=i["platform"],
            device_kind=i["device_kind"], device_count=i["device_count"])
    serve.shutdown()
    for pid in infos:
        wait_gone(pid, "serve replica")
    return sharded


# --------------------------------------------------------------------------
# --model jamba: one prompt pass and eight decode steps of the full-width
# Jamba stack through its slot state, against the plain float32 reference

# Largest relative error of a logits row the check accepts: the benchmark's
# limit for its cell (perfbench/traffic/chat-burst-open-loop.json says where
# the sound and the int8 readings lie: 0.040-0.058 against 0.23-0.27)
JAMBA_LOGITS_REL_ERR = 0.115


def jamba_check(seed: int, rehearsal: bool) -> dict:
    """Runs in a worker that holds the chip: weights from the seed at the
    published widths (`perfbench/configs/jamba2-3b.json`; a rehearsal takes
    `HybridConfig.tiny_runs()`), two prompts of 150 and 229 tokens in one
    prompt pass of 2 x 256, their state written into slots 3 and 200 of 256,
    eight tokens decoded teacher-forced through the donated slot state; the
    logits of both prompts' last positions and of every decoded position
    against the reference's full forward."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.lib import jamba_model
    from perfbench.lib.manifest import load_py
    from ray_tpu.models import hybrid

    rep = _device_report()
    _require_chip(rep, rehearsal)
    if rehearsal:  # the CPU backend shows every virtual device
        rep["device_count"] = 1
    root = os.path.dirname(os.path.abspath(__file__))
    ref = load_py(os.path.join(root, "perfbench", "references", "jamba.py"))
    with open(os.path.join(root, "perfbench", "configs", "jamba2-3b.json")) as f:
        c = json.load(f)
    if rehearsal:
        c.update(hidden_size=64, intermediate_size=128, num_hidden_layers=8,
                 num_attention_heads=4, attn_layer_offset=2, attn_layer_period=4,
                 mamba_dt_rank=8, vocab_size=512, torch_dtype="float32")
    cfg = jamba_model.model_config(c)
    slots, max_len, bucket, steps = (8, 512, 256, 8) if rehearsal else (256, 1024, 256, 8)
    t0 = time.time()
    params = jamba_model.make_params(cfg, seed)
    cache = cfg.make_cache(slots, max_len)
    jax.block_until_ready((params, cache.state))
    rng = np.random.default_rng(seed)
    lens, at = [150, 229], [3, slots - 56 if not rehearsal else 5]
    whole = rng.integers(1, cfg.vocab_size, (2, bucket + steps)).astype(np.int32)
    prompt = np.zeros((2, bucket), np.int32)
    for j, n in enumerate(lens):
        prompt[j, :n] = whole[j, :n]
    lens_d = jnp.asarray(lens, jnp.int32)
    t1 = time.time()
    logits, rows = hybrid.prefill(params, jnp.asarray(prompt), lens_d, cfg)
    got = {(j, n - 1): np.asarray(logits[j]) for j, n in enumerate(lens)}
    lengths, tokens = cache.write(
        jnp.zeros((slots,), jnp.int32), jnp.zeros((slots,), jnp.int32),
        jnp.asarray(at, jnp.int32), rows, lens_d, jnp.zeros((2,), jnp.int32))
    t2 = time.time()
    for t in range(steps):
        toks = np.zeros((slots,), np.int32)
        for j, n in enumerate(lens):
            toks[at[j]] = whole[j, n + t]
        cache.state, logits, _ = hybrid.decode_logits(
            params, cache.state, lengths, jnp.asarray(toks), None, cfg, 256)
        lengths = lengths + (lengths > 0)
        logits = np.asarray(logits)
        for j, n in enumerate(lens):
            got[(j, n + t)] = logits[at[j]]
    t3 = time.time()
    # the hot step, sampling on device: timed over the second ten of twenty
    step_ms = []
    for i in range(20):
        a = time.perf_counter()
        lengths, tokens, _ = cache.decode(params, lengths, tokens, 256, ())
        jax.block_until_ready(tokens)
        step_ms.append(1e3 * (time.perf_counter() - a))
    errs = {}
    for j, n in enumerate(lens):
        toks = np.zeros((1, bucket + steps), np.int32)
        toks[0, :n + steps] = whole[j, :n + steps]
        want = jax.jit(lambda p, t: ref.logits(p, t, c))(params, jnp.asarray(toks))[0]
        for (jj, pos), row in got.items():
            if jj == j:
                errs[f"{j}:{pos}"] = float(ref.rel_err(jnp.asarray(row), want[pos]))
    return {**rep, "params": int(sum(a.size for a in jax.tree_util.tree_leaves(params))),
            "init_s": t1 - t0, "prefill_and_write_s": t2 - t1,
            "eight_decode_logits_s": t3 - t2,
            "decode_step_ms_p50": float(np.median(step_ms[10:])),
            "logits_rel_err_max": max(errs.values()), "logits_rel_err": errs,
            "peak_bytes": int((jax.devices()[0].memory_stats() or {}).get(
                "peak_bytes_in_use", 0))}


def run_jamba(args) -> dict:
    task = ray_tpu.remote(resources={"TPU": 1}, num_cpus=0)(jamba_check)
    rep = ray_tpu.get(task.remote(args.seed, args.cpu_rehearsal), timeout=1500)
    check_device(rep, "jamba", 1, args.cpu_rehearsal)
    say("jamba", **{k: v for k, v in rep.items() if k != "logits_rel_err"})
    say("jamba", logits_rel_err=rep["logits_rel_err"])
    require(rep["logits_rel_err_max"] < (1e-4 if args.cpu_rehearsal
                                         else JAMBA_LOGITS_REL_ERR),
            f"the Jamba stack's logits are off the reference's by "
            f"{rep['logits_rel_err_max']:.4g} (relative)")
    return rep


# --------------------------------------------------------------------------
# --model pangu: one prompt pass and eight verified positions of the
# full-width openPangu-Ultra-MoE cut through its slot state, the prediction
# module with them, against the plain float32 reference


def pangu_check(seed: int, rehearsal: bool) -> dict:
    """Runs in a worker that holds the chip: weights from the seed at the
    published widths (`perfbench/configs/openpangu-ultra-moe-718b.1of32.json`;
    a rehearsal takes `HybridConfig.tiny_rotary()`), two prompts of 1500 and
    1999 tokens in one prompt pass of 2 x 2048, their latent rows (the
    module's with them) written into slots 3 and 20 of 32, then the verify
    step, two positions a slot, teacher-forced through the donated slot
    state, the slots advanced by 2, 1, 2, 1, 2 (a draft that held, a refused
    one whose row is overwritten): the main logits and the module's of both
    prompts' last positions and of every verified position against the
    reference's full forward under the program's choice of experts. Limits:
    the benchmark cell's own (`perfbench/traffic/longctx-open-loop.json`)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.lib import pangu_model
    from perfbench.lib.manifest import load_py
    from ray_tpu.models import hybrid

    rep = _device_report()
    _require_chip(rep, rehearsal)
    if rehearsal:  # the CPU backend shows every virtual device
        rep["device_count"] = 1
    root = os.path.dirname(os.path.abspath(__file__))
    ref = load_py(os.path.join(root, "perfbench", "references",
                               "openpangu_ultra_moe.py"))
    with open(os.path.join(root, "perfbench", "configs",
                           "openpangu-ultra-moe-718b.1of32.json")) as f:
        c = json.load(f)
    with open(os.path.join(root, "perfbench", "traffic",
                           "longctx-open-loop.json")) as f:
        limits = json.load(f)["limits"]
    if rehearsal:
        c.update(hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
                 num_hidden_layers=3, num_attention_heads=8, kv_lora_rank=32,
                 q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, num_experts_per_tok=2, vocab_size=512,
                 torch_dtype="float32",
                 experts_held={"of": 8, "first": 0, "count": 8})
    cfg = pangu_model.model_config(c)
    slots, max_len, bucket, lens, at = (8, 256, 64, [37, 50], [3, 5]) if rehearsal \
        else (32, 8192, 2048, [1500, 1999], [3, 20])
    keeps, steps = (2, 1, 2, 1, 2), 8
    total = bucket + (64 if rehearsal else 128)   # the reference goes 128 rows at a time
    t0 = time.time()
    params = pangu_model.make_params(cfg, seed)
    cache = cfg.make_cache(slots, max_len)
    jax.block_until_ready((params, cache.state))
    rng = np.random.default_rng(seed)
    whole = rng.integers(1, cfg.vocab_size, (2, total)).astype(np.int32)
    prompt = np.zeros((2, bucket), np.int32)
    for j, n in enumerate(lens):
        prompt[j, :n] = whole[j, :n]
    lens_d = jnp.asarray(lens, jnp.int32)
    t1 = time.time()
    # teacher-forced: the module at a prompt's last position is fed `whole`'s
    # next token, as the reference will be, not this pass's own choice
    logits, rows = hybrid.prefill(
        params, jnp.asarray(prompt), lens_d, cfg, with_routing=True,
        first=jnp.asarray([whole[j, n] for j, n in enumerate(lens)], jnp.int32))
    routing = np.asarray(rows.pop("routing"))
    module = np.asarray(rows.pop("mtp_logits"))
    got = {(j, n - 1): np.asarray(logits[j]) for j, n in enumerate(lens)}
    got_mtp = {(j, n - 1): module[j] for j, n in enumerate(lens)}
    chose = [{p: routing[:, j, p] for p in range(n)} for j, n in enumerate(lens)]
    lengths, tokens = cache.write(
        jnp.zeros((slots,), jnp.int32), jnp.zeros((slots,), jnp.int32),
        jnp.asarray(at, jnp.int32), rows, lens_d, jnp.zeros((2,), jnp.int32))
    active = np.zeros((slots,), bool)
    active[at] = True
    pos = list(lens)
    attn_len = 64 if rehearsal else 2048
    t2 = time.time()
    for keep in keeps:
        toks = np.zeros((slots, 2), np.int32)
        nxt = np.zeros((slots, 2), np.int32)
        for j in range(2):
            toks[at[j]] = whole[j, pos[j]:pos[j] + 2]
            nxt[at[j]] = whole[j, pos[j] + 1:pos[j] + 3]
        cache.state, main, module, picked = hybrid.verify_logits(
            params, cache.state, lengths, jnp.asarray(toks), jnp.asarray(nxt),
            jnp.asarray(active), cfg, attn_len)
        main, module, picked = (np.asarray(a) for a in (main, module, picked))
        for j in range(2):
            for a in range(2):
                got[(j, pos[j] + a)] = main[at[j], a]
                got_mtp[(j, pos[j] + a)] = module[at[j], a]
                chose[j][pos[j] + a] = picked[:, at[j], a]
            pos[j] += keep
        lengths = lengths + keep * jnp.asarray(active, jnp.int32)
    t3 = time.time()
    # the hot step, drafting and sampling on device: the second ten of twenty
    step_ms = []
    for i in range(20):
        a = time.perf_counter()
        lengths, tokens, report = cache.decode(params, lengths, tokens, attn_len, at)
        jax.block_until_ready(report)
        step_ms.append(1e3 * (time.perf_counter() - a))
    errs, errs_mtp, margin = {}, {}, 0.0
    layers, k = routing.shape[0], routing.shape[-1]
    routed = jax.jit(lambda p, t, r: ref.logits_routed(p, t, c, r))
    for j in range(2):
        forced = np.full((layers, 1, total, k), -1, np.int32)
        for p, picked in chose[j].items():
            forced[:, 0, p] = picked
        want, want_mtp, worst = routed(params, jnp.asarray(whole[j:j + 1]),
                                       jnp.asarray(forced))
        margin = max(margin, float(worst))
        for (jj, p), row in got.items():
            if jj == j:
                errs[f"{j}:{p}"] = float(ref.rel_err(jnp.asarray(row), want[0, p]))
        for (jj, p), row in got_mtp.items():
            if jj == j:
                errs_mtp[f"{j}:{p}"] = float(ref.rel_err(jnp.asarray(row), want_mtp[0, p]))
    assert pos[0] == lens[0] + steps
    return {**rep, "params": int(sum(a.size for a in jax.tree_util.tree_leaves(params))),
            "init_s": t1 - t0, "prefill_and_write_s": t2 - t1,
            "verify_steps_s": t3 - t2,
            "decode_step_ms_p50": float(np.median(step_ms[10:])),
            "logits_rel_err_max": max(errs.values()),
            "mtp_logits_rel_err_max": max(errs_mtp.values()),
            "route_margin_max": margin, "logits_rel_err": errs,
            "limits": limits,
            "peak_bytes": int((jax.devices()[0].memory_stats() or {}).get(
                "peak_bytes_in_use", 0))}


def run_pangu(args) -> dict:
    task = ray_tpu.remote(resources={"TPU": 1}, num_cpus=0)(pangu_check)
    rep = ray_tpu.get(task.remote(args.seed, args.cpu_rehearsal), timeout=3000)
    check_device(rep, "pangu", 1, args.cpu_rehearsal)
    say("pangu", **{k: v for k, v in rep.items() if k != "logits_rel_err"})
    say("pangu", logits_rel_err=rep["logits_rel_err"])
    for name, key in (("logits_rel_err_max", "prefill_logits_rel_err"),
                      ("mtp_logits_rel_err_max", "mtp_logits_rel_err"),
                      ("route_margin_max", "route_margin_max")):
        limit = 1e-4 if args.cpu_rehearsal else rep["limits"][key]
        require(rep[name] < limit,
                f"the openPangu stack's {name} is {rep[name]:.4g}, limit {limit:g}")
    return rep


def main() -> None:
    global TAG
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--model", choices=("b1", "jamba", "pangu"), default="b1",
                    help="jamba / pangu: only that stack against its reference")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny model on the CPU; proves nothing about the chip")
    args = ap.parse_args()

    if args.cpu_rehearsal:
        TAG = "[CPU REHEARSAL] "
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                                   " --xla_force_host_platform_device_count=4")
    # placed from outside if the variable is set; else one fixed directory
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", default_compile_cache_dir())
    say("parent", pid=os.getpid(), seed=args.seed, chips=args.chips,
        python=sys.version.split()[0],
        compile_cache_dir=os.environ["JAX_COMPILATION_CACHE_DIR"],
        jax_platforms=os.environ.get("JAX_PLATFORMS"))

    from ray_tpu.core import arena, native_scheduler
    from ray_tpu.data import token_loader

    native = {"arena": arena.available(),
              "scheduler": native_scheduler.available(),
              "loader": token_loader.native_available()}
    say("parent", native_modules_built_and_loaded=native)
    require(native["arena"], "the native arena did not build or load")

    ray_tpu.init(num_cpus=8, resources={"TPU": args.chips})
    try:
        if args.model == "jamba":
            rep = run_jamba(args)
        elif args.model == "pangu":
            rep = run_pangu(args)
        elif args.chips == 4:
            rep = run_four_chips(args)
        else:
            rep = run_train(args, 1, {"dp": 1}, "train")
            run_serve(args)
    finally:
        ray_tpu.shutdown()
    device = {"platform": rep["platform"], "kind": rep["device_kind"],
              "count": rep["device_count"]}

    require("jax" not in sys.modules, "the parent process imported jax")
    say("parent", jax_imported=False, ok=not args.cpu_rehearsal)
    if args.cpu_rehearsal:
        print(f"{TAG}rehearsal finished; this is not a chip result", flush=True)
        sys.exit(10)
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
