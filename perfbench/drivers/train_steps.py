"""Driver of `token_batches` traffic: the training step, back to back.

The parent (never on jax) asks `JaxTrainer` for ONE worker with the cell's
chips; `train_loop` runs there: weights and optimizer state from the seed,
the program's own jitted step (`train.make_train_step`), every shape warmed,
then steps for the window with the host clock around each
`block_until_ready`. After the window the state is dropped and the program's
forward and backward are compared with the plain float32 reference on a
seeded sample. A traced run wraps `trace_steps` whole steps of the window in
the profiler and reduces the trace before it reports.
"""

from __future__ import annotations

import os
import shutil
import time


def _program(c: dict, seq: int, n_layers: int):
    """The program's configuration, mesh, optimizer, state shardings and step
    for this cell at `n_layers` and `seq` (in the process that holds the chips)."""
    import jax

    from perfbench.lib import model
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.train import batch_sharding, make_train_step
    from ray_tpu.train.step import default_optimizer

    run = c["config"]["run"]
    cfg = model.model_config(
        c["config"], n_layers=n_layers, max_seq_len=seq, remat=run["remat"],
        loss_chunk=0, fused_ffn=run["fused_blocks"], fused_attn=run["fused_blocks"])
    mesh = make_mesh(MeshConfig(**run["mesh"]), jax.devices()[:c["chips"]])
    opt = default_optimizer()
    step_fn, _, sh = make_train_step(cfg, mesh, opt)
    return cfg, mesh, opt, sh, batch_sharding(mesh), step_fn


def _check_program(c: dict):
    """The same at the depth and length the reference is compared at."""
    depth = min(c["config"]["num_hidden_layers"], c["config"]["run"]["check_layers"])
    cfg, mesh, _, sh, b_sh, _ = _program(c, c["traffic"]["check_seq"], depth)
    return cfg, dict(c["config"], num_hidden_layers=depth), mesh, sh.params, b_sh


def train_loop(c: dict) -> None:
    """Runs in the worker that holds the chips. Reports one dict."""
    t_enter = time.time()
    import math

    import jax
    import jax.numpy as jnp

    from perfbench.lib import model, traffic, worker, xplane
    from perfbench.lib.manifest import load_py
    from ray_tpu.air import session
    from ray_tpu.train.step import TrainState

    counter = worker.CompileCounter()
    spans = worker.Spans()
    chips, tr = c["chips"], c["traffic"]
    out = {"device": worker.device_report(chips, c["rehearsal"])}
    t_device = time.time()
    seq, batch = tr["seq"], tr["batch"]
    cfg, _, opt, sh, b_sh, step_fn = _program(c, seq, c["config"]["num_hidden_layers"])
    state = jax.jit(
        lambda p: TrainState(p, opt.init(p), jnp.zeros((), jnp.int32)),
        out_shardings=sh, donate_argnums=0)(
            model.make_params(cfg, c["seed"], sh.params))
    pool = traffic.token_batches(tr, c["seed"], cfg.vocab_size)

    def put(i):
        t = pool[i % len(pool)]
        return {"inputs": jax.device_put(t[:, :-1], b_sh["inputs"]),
                "targets": jax.device_put(t[:, 1:], b_sh["targets"])}

    compiled = step_fn.lower(state, put(0)).compile()
    losses = []
    for i in range(tr["warm_steps"]):
        state, m = compiled(state, put(i))
        losses.append(float(m["loss"]))
    setup = counter.snapshot()

    # ---------------------------------------------------------- the window
    trace_dir = os.path.join(c["out_dir"], "trace")
    trace_at = (tr["trace_from_step"], tr["trace_from_step"] + tr["trace_steps"]) \
        if c["trace"] else (-1, -1)
    step_end, n = [], len(losses)
    t_open = time.time()
    t0 = time.perf_counter()
    while True:
        k = len(step_end)
        if k == trace_at[0]:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
        with spans.span("bench.wait_input"):
            b = put(n + k)
        with spans.span("bench.train_step"):
            state, m = compiled(state, b)
            jax.block_until_ready(m)
        step_end.append(time.perf_counter() - t0)
        losses.append(float(m["loss"]))
        if k + 1 == trace_at[1]:
            jax.profiler.stop_trace()
        if step_end[-1] >= c["seconds"] and k + 1 >= trace_at[1]:
            break
    after = counter.snapshot()
    out.update({
        "t_enter": t_enter, "t_device": t_device, "t_open": t_open,
        "steps": len(step_end), "window_s": step_end[-1],
        "tokens_per_step": batch * seq, "seq": seq, "step_end_s": step_end,
        "losses": losses, "ln_vocab": math.log(cfg.vocab_size),
        "compile_setup": setup,
        "compiles_in_window": after["lowerings"] - setup["lowerings"],
        "spans": {k: [(a, b) for a, b, _ in v] for k, v in spans.rows.items()},
        "memory_peak_bytes": worker.memory_peak_bytes(chips),
    })
    if c["trace"]:
        out["trace"] = xplane.reduce_dir(trace_dir, c["rehearsal"])
        if out["trace"]:
            out["trace"]["traced_steps"] = tr["trace_steps"]

    # ------------------- correctness, outside the window: drop the state
    del state, m, b, compiled
    sample = traffic.sample_tokens(c["seed"], cfg.vocab_size, tr["check_batch"],
                                   tr["check_seq"])
    out["checks"] = check(load_py(c["reference_file"]), *_check_program(c), sample,
                          c["seed"], tr["check_wrt"], c.get("control"))
    session.report(out)


def control_loop(c: dict) -> None:
    """For `perfbench/control.py`: in one worker, over several seeds, the
    program's check and the control's, at the cell's own size."""
    from perfbench.lib import traffic, worker
    from perfbench.lib.manifest import load_py
    from ray_tpu.air import session

    tr = c["traffic"]
    dev = worker.device_report(c["chips"], c["rehearsal"])
    ref, program = load_py(c["reference_file"]), _check_program(c)
    out = []
    for seed, mode in c["runs"]:
        sample = traffic.sample_tokens(seed, program[0].vocab_size,
                                       tr["check_batch"], tr["check_seq"])
        res = check(ref, *program, sample, seed, tr["check_wrt"], mode)
        out.append({"seed": seed, "mode": mode or "program", **res})
    session.report({"device": dev, "readings": out})


def check(ref, cfg, c_file, mesh, p_sh, b_sh, sample, seed, wrt, control):
    """The program's logits and gradients (on its mesh, in its precision,
    through its kernels) against the float32 reference on ONE device, on the
    same weights and tokens. With `control`, the reference in that lower
    precision stands in the program's place."""
    import jax
    import jax.numpy as jnp

    from perfbench.lib import model
    from ray_tpu.models.transformer import forward, loss_fn

    params = model.make_params(cfg, seed, p_sh)
    inputs, targets = sample[:, :-1], sample[:, 1:]
    one = mesh.devices.ravel()[0]
    p_one = jax.device_put(params, one)
    i_one, t_one = jax.device_put(inputs, one), jax.device_put(targets, one)
    want_logits = jax.jit(lambda p, i: ref.logits(p, i, c_file))(p_one, i_one)
    want_loss, want_g = jax.jit(
        lambda p, i, t: ref.loss_and_grads(p, i, t, c_file, wrt))(p_one, i_one, t_one)
    if control:
        p_low = jax.jit(lambda p: ref.lower_precision(p, control))(p_one)
        got_logits = jax.jit(lambda p, i: ref.logits(p, i, c_file))(p_low, i_one)
        got_loss, got_g = jax.jit(
            lambda p, i, t: ref.loss_and_grads(p, i, t, c_file, wrt))(p_low, i_one, t_one)
        del p_low
    else:
        batch = {"inputs": jax.device_put(inputs, b_sh["inputs"]),
                 "targets": jax.device_put(targets, b_sh["targets"])}
        got_logits = jax.jit(lambda p, i: forward(p, i, cfg, mesh=mesh))(
            params, batch["inputs"])
        got_loss, grads = jax.jit(jax.value_and_grad(
            lambda p, b: loss_fn(p, b, cfg, mesh)[0]))(params, batch)
        got_g = {k: ref._get(grads, k) for k in wrt}
        del grads
    to_one = lambda a: jax.device_put(a, one)
    return {
        "fwd_logits_rel_err": float(ref.rel_err(to_one(got_logits), want_logits)),
        "bwd_grad_rel_err": max(float(ref.rel_err(to_one(got_g[k]), want_g[k]))
                                for k in wrt),
        "loss_abs_err": abs(float(got_loss) - float(want_loss)),
        "reference_loss": float(want_loss), "control": control or "",
        "check_layers": cfg.n_layers,
    }


def run(ctx) -> dict:
    """In the parent. Returns the run's record for the metric readers."""
    import ray_tpu
    from ray_tpu.air import ScalingConfig
    from ray_tpu.train import JaxTrainer

    cell, limits = ctx["cell"], ctx["traffic"]["limits"]
    ray_tpu.init(num_cpus=8, resources={"TPU": cell["chips"]})
    try:
        t_ask = time.time()
        result = JaxTrainer(
            train_loop,
            train_loop_config={k: ctx[k] for k in (
                "config", "traffic", "seed", "seconds", "trace", "rehearsal",
                "out_dir", "reference_file", "control")} | {"chips": cell["chips"]},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True,
                                         chips_per_worker=cell["chips"])).fit()
    finally:
        ray_tpu.shutdown()
    if result.error is not None:
        raise SystemExit(f"perfbench: the train worker failed: {result.error}")
    rec = dict(result.metrics)
    rec["t_ask"] = t_ask
    losses, ck = rec["losses"], rec["checks"]
    first, last = losses[0], sum(losses[-4:]) / 4
    compared = [
        ("fwd_logits_rel_err", ck["fwd_logits_rel_err"], limits["fwd_logits_rel_err"]),
        ("bwd_grad_rel_err", ck["bwd_grad_rel_err"], limits["bwd_grad_rel_err"]),
        ("first_loss_minus_ln_vocab", abs(first - rec["ln_vocab"]),
         limits["first_loss_minus_ln_vocab"]),
        ("last_over_first_loss", last / first, limits["last_over_first_loss"]),
    ]
    finite = all(l == l and abs(l) < 1e9 for l in losses)
    print(f"[correct] losses finite: {finite}; first {first:.4f} "
          f"(ln vocab {rec['ln_vocab']:.4f}), mean of last four {last:.4f} "
          f"over {len(losses)} steps", flush=True)
    rec.update({"compared": compared, "correct_extra": finite,
                "attempted": rec["steps"], "failed": 0})
    return rec
