"""The four-chip cell's train step in two forms, in ONE process that holds
the four chips: the same state, the same batches, each form compiled and
timed in turn (host clock around steps that end in `block_until_ready`),
some traced. A form is the program with one name of it replaced HERE (the
program has no option for it):

    parent   `models/transformer._NORM_SCALES` empty: the two norm scales go
             into the scan as they are and the partitioner all-reduces their
             gradients inside the backward's body (the text of commit 44a087d)
    change   the program as it stands: the scales ride once a rank, the
             partial sums leave the scan and are summed once a step
    regions  `change` with each rank reading its own copy of a scale inside
             a `shard_map` manual over the axes that split the rows, where
             the program reshapes (the tree of calls 1 to 3 had it so; from
             call 4 on)

    python ci/chip_calls/pr46/step_forms.py --forms parent,change,change,parent \
        --steps 12 --trace parent,change --out chiprun_out/pr46/call1

Prints one JSON line a form, then `--sum-check`: how the chip adds the
ranks' float32 partials that it hands on as bfloat16 (the compiled step's
one all-reduce behind the scan takes float32 operands and yields bfloat16).
Not the benchmark: no trainer, no worker, no check against the reference;
the cell's numbers come from `perfbench/run.py`. `exposed.py` is PR 38's.
"""

from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import shutil
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))


@contextlib.contextmanager
def replaced(module, name, value):
    was = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, was)


def forms():
    import jax
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models import transformer
    from ray_tpu.parallel import fsdp

    def apply_in_a_region(self, norm, x):
        axes = fsdp.BATCH_AXES + ((self.seq_axis,) if self.seq_axis else ())
        x_spec = P(fsdp.BATCH_AXES, self.seq_axis)
        return jax.shard_map(
            lambda x, rows: norm(x, rows[0]), mesh=self.mesh,
            axis_names=set(axes), in_specs=(x_spec, P(axes)),
            out_specs=x_spec, check_vma=False)(x, self.rows)

    return {
        "parent": lambda: replaced(transformer, "_NORM_SCALES", ()),
        "change": contextlib.nullcontext,
        "regions": lambda: replaced(fsdp.UnreducedScale, "apply", apply_in_a_region),
    }


def sum_check(mesh):
    """Four float32 partials a chip-row, summed over the ranks and handed on
    as bfloat16, as the step's all-reduce behind the scan does: the result
    beside float32-sum-rounded-once and beside the rounded partials added in
    bfloat16 (what an all-reduce of bf16 operands gives)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    axes = ("dp", "fsdp", "tp")
    parts = jax.random.normal(jax.random.PRNGKey(5), (22, 4, 4096), jnp.float32)
    parts = parts * jnp.asarray([1.0, -0.9, 0.37, 0.011])[None, :, None]
    placed = jax.device_put(parts, NamedSharding(mesh, P(None, axes)))
    got = jax.jit(lambda p: p.sum(1).astype(jnp.bfloat16),
                  out_shardings=NamedSharding(mesh, P()))
    text = got.lower(placed).compile().as_text()
    line = next((l for l in text.splitlines() if " all-reduce(" in l), "")
    got = np.asarray(got(placed).astype(jnp.float32))
    exact = np.asarray(parts, np.float64).sum(1)
    once = np.asarray(jnp.asarray(exact, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32))
    rounded = parts.astype(jnp.bfloat16)
    in_bf16 = np.asarray(((rounded[:, 0] + rounded[:, 1]) + rounded[:, 2]
                          + rounded[:, 3]).astype(jnp.float32))
    far = lambda a: float(np.abs(a - exact).mean())
    return {"sum_check": {
        "all_reduce": line.strip()[:160],
        "equal_to_float32_sum_rounded_once": float((got == once).mean()),
        "equal_to_partials_added_in_bf16": float((got == in_bf16).mean()),
        "mean_abs_err": far(got), "mean_abs_err_rounded_once": far(once),
        "mean_abs_err_added_in_bf16": far(in_bf16)}}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--forms", default="parent,change")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--trace", default="")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--seed", type=int, default=2468013579)
    ap.add_argument("--out", default="chiprun_out/pr46/forms")
    ap.add_argument("--sum-check", action="store_true")
    ap.add_argument("--keep-trace", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="toy widths: this script's control flow on the CPU")
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from ci.chip_calls.pr38 import exposed
    from perfbench.lib import model, traffic, xplane
    from ray_tpu.parallel import MeshConfig, make_mesh
    from ray_tpu.train import batch_sharding, make_train_step
    from ray_tpu.train.step import TrainState, default_optimizer

    os.makedirs(a.out, exist_ok=True)
    conf = json.load(open("perfbench/configs/mistral-7b-v0.3.4chip.json"))
    tr = json.load(open("perfbench/traffic/pretrain-2x2048.json"))
    run = conf["run"]
    cfg = model.model_config(
        conf, n_layers=a.layers or conf["num_hidden_layers"],
        max_seq_len=tr["seq"], remat=run["remat"], loss_chunk=0,
        fused_ffn=False, fused_attn=False)
    if a.tiny:
        import dataclasses
        cfg = dataclasses.replace(cfg, vocab_size=512, d_model=128, n_layers=2,
                                  n_heads=4, n_kv_heads=2, d_ff=256,
                                  max_seq_len=64)
        tr = dict(tr, seq=64)
    devs = jax.devices()
    print(json.dumps({"device_kind": devs[0].device_kind, "n": len(devs)}),
          flush=True)
    mesh = make_mesh(MeshConfig(**run["mesh"]), devs[:4])
    opt = default_optimizer()
    _, _, sh = make_train_step(cfg, mesh, opt)
    b_sh = batch_sharding(mesh)
    state = jax.jit(
        lambda p: TrainState(p, opt.init(p), jnp.zeros((), jnp.int32)),
        out_shardings=sh, donate_argnums=0)(
            model.make_params(cfg, a.seed, sh.params))
    pool = traffic.token_batches(tr, a.seed, cfg.vocab_size)

    def put(i):
        t = pool[i % len(pool)]
        return {"inputs": jax.device_put(t[:, :-1], b_sh["inputs"]),
                "targets": jax.device_put(t[:, 1:], b_sh["targets"])}

    traced = set(filter(None, a.trace.split(",")))
    table = forms()
    for k, form in enumerate(a.forms.split(",")):
        try:
            with table[form]():
                step_fn, _, _ = make_train_step(cfg, mesh, opt)
                t0 = time.time()
                compiled = step_fn.lower(state, put(0)).compile()
                compile_s = time.time() - t0
        except Exception as e:  # a form the compiler refuses: say so, go on
            print(json.dumps({"form": form, "k": k, "refused": repr(e)[:600]}),
                  flush=True)
            continue
        mem = compiled.memory_analysis()
        for i in range(2):
            state, m = compiled(state, put(i))
        jax.block_until_ready(m)
        ms, losses = [], []
        for i in range(a.steps):
            b = put(2 + i)
            t0 = time.perf_counter()
            state, m = compiled(state, b)
            jax.block_until_ready(m)
            ms.append(1e3 * (time.perf_counter() - t0))
            losses.append(float(m["loss"]))
        row = {"form": form, "k": k, "compile_s": round(compile_s, 1),
               "step_ms_p50": statistics.median(ms), "step_ms_min": min(ms),
               "step_ms_max": max(ms), "loss_first": losses[0],
               "loss_last": losses[-1],
               "temp_bytes": mem.temp_size_in_bytes,
               "peak_bytes": max((d.memory_stats() or {}).get(
                   "peak_bytes_in_use", 0) for d in devs[:4])}
        if form in traced:
            traced.discard(form)
            trace_dir = os.path.join(a.out, f"trace_{form}")
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(trace_dir)
            for i in range(5):
                state, m = compiled(state, put(i))
                jax.block_until_ready(m)
            jax.profiler.stop_trace()
            planes = xplane.load(xplane.find_xplane(trace_dir))
            if a.tiny:  # a CPU trace has no device plane to reduce
                print(json.dumps(row), flush=True)
                continue
            red = exposed.reduce(planes, top=400)
            red["exposed_share_pct_benchmark"] = (
                100 * xplane.reduce(planes)["exposed_collective_s"]
                / red["window_s"])
            with open(os.path.join(a.out, f"trace_{form}.json"), "w") as f:
                json.dump(red, f, indent=1)
            row.update({k2: red[k2] for k2 in red if k2 != "ops"})
            row["collective_ops_ms_per_step"] = [
                [o[0][:44], round(200 * o[2], 2)] for o in red["ops"]
                if o[1] == "collective" and o[2] > 0.0005]
            if not a.keep_trace:
                shutil.rmtree(trace_dir, ignore_errors=True)
        print(json.dumps(row), flush=True)
        del compiled
    if a.sum_check:
        print(json.dumps(sum_check(mesh)), flush=True)


if __name__ == "__main__":
    main()
