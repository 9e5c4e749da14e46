"""The four-chip cell's train step in several forms, in ONE process that
holds the four chips: the same state, the same batches, each form compiled
and timed in turn (host clock around steps that end in `block_until_ready`),
some traced. A form is the program with one function of it replaced HERE
(the program has no option for it):

    parent   `models/transformer._rows_mesh` says no: the partitioner's four
             tp all-reduces a layer, the text of commit d52e00f
    change   the program as it stands
    plain    `fsdp.ring_products` replaced by plain products: the residual
             rides over tp as in `change`, the weights' gathers over fsdp are
             the partitioner's (tried, not kept: calls 1 and 2)
    rings_only  the other half alone: the parent's four tp all-reduces a
             layer (`_rows_mesh` says no), with `x @ ExchangedWeight` and its
             dx made `fsdp.ring_products` (asked for by the review of PR 38)
    bf16_partials  `change` with the partial sums over fsdp's shards taken
             and added in bfloat16, as the tree of calls 1 to 5 had them
             (refused by the review: the partitioner's product sums in float32)
    sum_fused  `tp._ring_sum` without its barrier: the compiler fuses the sum
             over tp into the own-rows product, which then waits for the
             transfer (tried, not kept: call 2)
    (calls 1 and 2 ran the trees of their hour, whose forms README.md names)

    python ci/chip_calls/pr38/step_forms.py --forms parent,change,parent,change \
        --steps 12 --trace change --out chiprun_out/pr38/call1

Prints one JSON line a form. Not the benchmark: no trainer, no worker, no
check against the reference; the cell's numbers come from `perfbench/run.py`.
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
    from jax.ad_checkpoint import checkpoint_name
    from jax.sharding import PartitionSpec as P

    from ray_tpu.models import transformer
    from ray_tpu.parallel import fsdp, tp

    def ring_sum_fused(part):
        n = jax.lax.axis_size(tp.AXIS)
        acc = part(1 % n)
        for t in range(2, n + 1):
            acc = jax.lax.ppermute(acc, tp.AXIS, tp._ring()) + part(t % n)
        return acc

    def plain_products(groups, ws, dim, transposed, mesh):
        # whole over the axes that stay the partitioner's: it gathers the
        # weight over fsdp once, for the products of every chunk
        ws = [jax.lax.with_sharding_constraint(w, P()) for w in ws]
        return [sum(fsdp._dot(x, w, int(transposed)) for x, w in zip(group, ws))
                for group in groups]

    def ring_product(x, w, dim, mesh):
        # (named as tp.py names its products: else remat="dots" keeps the
        # float32 partial products of every layer, 2.2 GB too many)
        return checkpoint_name(
            fsdp.ring_products([[x]], [w], dim, False, mesh)[0], tp.SAVED)

    def ring_matmul_bwd(dim, mesh, res, dy):
        x, w = res
        dx, = fsdp.ring_products([[dy]], [w], dim, True, mesh)
        return dx, fsdp.weight_grad(x, dy, dim, mesh).astype(w.dtype)

    ring_matmul = jax.custom_vjp(ring_product, nondiff_argnums=(2, 3))
    ring_matmul.defvjp(lambda x, w, dim, mesh: (ring_product(x, w, dim, mesh), (x, w)),
                       ring_matmul_bwd)

    @contextlib.contextmanager
    def rings_only():
        with replaced(transformer, "_rows_mesh", lambda *a: None), \
                replaced(fsdp, "_matmul", ring_matmul):
            yield

    dot = fsdp._dot
    return {
        "parent": lambda: replaced(transformer, "_rows_mesh", lambda *a: None),
        "change": contextlib.nullcontext,
        "plain": lambda: replaced(fsdp, "ring_products", plain_products),
        "rings_only": rings_only,
        "bf16_partials": lambda: replaced(
            fsdp, "_dot", lambda x, w, summed, dtype=None: dot(x, w, summed)),
        "sum_fused": lambda: replaced(tp, "_ring_sum", ring_sum_fused),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--forms", default="parent,change")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--trace", default="")
    ap.add_argument("--layers", type=int, default=0)
    ap.add_argument("--seed", type=int, default=2468013579)
    ap.add_argument("--out", default="chiprun_out/pr38/forms")
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


if __name__ == "__main__":
    main()
