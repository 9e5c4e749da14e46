"""Spellings of the dense decode step's q/k/v projections, side by side.

`--compile`: each form's step compiled for the described v5e at the chat
cell's shapes (no chip): the layer scan's top-level instructions.
default: each form's step timed on the chip this process holds (the cell's
shapes, random bf16 weights; FILL busy slots of 32 at ROWS live rows), then
one profiler trace a form, reduced to device time per op name.

The program holds ONE form (`serving._one_row_qkv`); the others live here.
"""
import argparse
import collections
import glob
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "../../..")))
ap = argparse.ArgumentParser()
ap.add_argument("--compile", action="store_true")
ap.add_argument("--forms", default="parent,barrier,roll,stacked")
ap.add_argument("--attn-len", type=int, default=512)
ap.add_argument("--fill", default="4,32")
ap.add_argument("--rows", type=int, default=330)
ap.add_argument("--steps", type=int, default=300)
ap.add_argument("--out", default="chiprun_out/pr35")
args = ap.parse_args()
if args.compile:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import ModelConfig, serving
from ray_tpu.models.transformer import _project_qkv, init_params
from ray_tpu.ops.layers import apply_rotary, rms_norm

CFG = ModelConfig(vocab_size=92544, d_model=2048, n_layers=24, n_heads=16,
                  n_kv_heads=8, d_ff=8192, rope_theta=1e6)
SLOTS, MAX_LEN = 32, 1024


def parent(cfg, p, x, cos, sin):
    q, k, v = _project_qkv(cfg, p, x, cos, sin)
    B = x.shape[0]
    return (q[:, 0].reshape(B, cfg.n_kv_heads, -1, cfg.head_dim),
            k[:, 0].astype(cfg.dtype), v[:, 0].astype(cfg.dtype))


def _roll_rotary(x, cos, sin):
    """x [B, heads, hd] bf16; cos, sin [B, 1, hd/2]: the same float32
    products and sums as `apply_rotary`, the halves swapped by a roll."""
    xf = x.astype(jnp.float32)
    cos2 = jnp.concatenate([cos, cos], axis=-1)
    sin2 = jnp.concatenate([-sin, sin], axis=-1)
    out = xf * cos2 + jnp.roll(xf, x.shape[-1] // 2, axis=-1) * sin2
    return out.astype(x.dtype)


def roll(cfg, p, x, cos, sin):
    B = x.shape[0]
    rep = cfg.n_heads // cfg.n_kv_heads
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)[:, 0]
    q, k, v = h @ p["wq"], h @ p["wk"], h @ p["wv"]
    q, k = jax.lax.optimization_barrier((q, k))
    q = _roll_rotary(q.reshape(B, cfg.n_heads, cfg.head_dim), cos, sin)
    k = _roll_rotary(k.reshape(B, cfg.n_kv_heads, cfg.head_dim), cos, sin)
    return (q.reshape(B, cfg.n_kv_heads, rep, cfg.head_dim), k,
            v.reshape(B, cfg.n_kv_heads, cfg.head_dim))


def stacked(cfg, p, x, cos, sin):
    """One product against `wq|wk|wv` laid side by side, [d, (h + 2 kvh) hd]."""
    B = x.shape[0]
    rep = cfg.n_heads // cfg.n_kv_heads
    nq, nk = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    h = rms_norm(x, p["attn_norm"], cfg.norm_eps)[:, 0]
    qkv = jax.lax.optimization_barrier(h @ p["wqkv"])
    q, k, v = qkv[:, :nq], qkv[:, nq:nq + nk], qkv[:, nq + nk:]
    q = apply_rotary(q.reshape(B, cfg.n_kv_heads, rep, cfg.head_dim), cos, sin)
    k = apply_rotary(k.reshape(B, 1, cfg.n_kv_heads, cfg.head_dim), cos, sin)
    return q, k[:, 0], v.reshape(B, cfg.n_kv_heads, cfg.head_dim)


FORMS = {"parent": parent, "barrier": serving._one_row_qkv, "roll": roll,
         "stacked": stacked}


def params_for(form, params):
    if form != "stacked":
        return params
    layers = dict(params["layers"])
    wq, wk, wv = layers.pop("wq"), layers.pop("wk"), layers.pop("wv")
    if isinstance(wq, jax.ShapeDtypeStruct):
        layers["wqkv"] = jax.ShapeDtypeStruct(
            wq.shape[:2] + (wq.shape[2] + wk.shape[2] + wv.shape[2],), wq.dtype,
            sharding=wq.sharding)
    else:
        layers["wqkv"] = jnp.concatenate([wq, wk, wv], axis=-1)
    return {**params, "layers": layers}


def step_of(form):
    """`decode_step_fused`'s own text around `form`'s projections. A function
    of its own a form: jax keeps traces by the function's identity."""
    def step(params, k_all, v_all, lengths, tokens, cfg, attn_len):
        serving._one_row_qkv = FORMS[form]
        return serving.decode_step_fused.__wrapped__(
            params, k_all, v_all, lengths, tokens, cfg, attn_len)

    return jax.jit(step, static_argnames=("cfg", "attn_len"),
                   donate_argnums=(1, 2, 3))


_LINE = re.compile(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) ([a-z][\w\-]*)\((.*)")


def body_rows(text):
    body = re.search(r"while\(.*body=%([\w.\-]+)", text).group(1)
    rows, on = [], False
    for line in text.splitlines():
        if re.match(r"(?:ENTRY )?%" + re.escape(body) + r" \(", line):
            on = True
        elif on and line.startswith("}"):
            break
        elif on:
            m = _LINE.match(line)
            if m and m.group(3) not in ("get-tuple-element", "constant", "bitcast",
                                        "tuple", "parameter"):
                cyc = re.search(r'"estimated_cycles":"(\d+)"', m.group(4))
                kind = re.search(r"kind=(\w+)", m.group(4))
                rows.append((m.group(1), m.group(2)[:48], m.group(3),
                             kind.group(1) if kind else "", int(cyc.group(1)) if cyc else 0))
    return rows


def compile_all():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from ray_tpu.ops.pallas import _util

    _util.on_tpu = lambda: True
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def chip(dims, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one)

    shapes = jax.eval_shape(lambda k: init_params(k, CFG), jax.random.PRNGKey(0))
    shapes = jax.tree_util.tree_map(lambda s: chip(s.shape, s.dtype), shapes)
    kv = chip((CFG.n_layers, SLOTS, CFG.n_kv_heads, MAX_LEN, CFG.head_dim))
    ints = chip((SLOTS,), jnp.int32)
    for form in args.forms.split(","):
        c = step_of(form).lower(params_for(form, shapes), kv, kv, ints, ints, CFG,
                                args.attn_len).compile()
        rows = body_rows(c.as_text())
        print(f"== {form}: {len(rows)} top-level instructions in the scan's body, "
              f"{sum(r[4] for r in rows)} estimated cycles")
        for r in rows:
            print(f"   {r[0]:42s} {r[1]:48s} {r[2]:14s} {r[3]:8s} {r[4]:7d}")


def device_ops(trace_dir):
    """Device time per op name over the newest trace under `trace_dir`."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")))[-1]
    data = ProfileData.from_file(path)
    ops = collections.defaultdict(lambda: [0, 0.0])
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                ops[ev.name][0] += 1
                ops[ev.name][1] += ev.duration_ns
    return ops


def measure():
    os.makedirs(args.out, exist_ok=True)
    dev = jax.devices()[0]
    assert dev.platform == "tpu", dev
    params = jax.jit(lambda k: init_params(k, CFG))(jax.random.PRNGKey(7))
    results = {"device_kind": dev.device_kind, "forms": {}}
    for form in args.forms.split(","):
        p = params_for(form, params)
        step = step_of(form)
        res = results["forms"][form] = {}
        for fill in map(int, args.fill.split(",")):
            shape = (CFG.n_layers, SLOTS, CFG.n_kv_heads, MAX_LEN, CFG.head_dim)
            k = jnp.zeros(shape, CFG.dtype) + 0.01
            v = jnp.zeros(shape, CFG.dtype) + 0.01
            lens = np.zeros(SLOTS, np.int32)
            lens[:fill] = args.rows
            tokens = jnp.arange(SLOTS, dtype=jnp.int32) + 5

            def run(n, k, v, tokens, lens=lens):
                for _ in range(n // 50):
                    lengths = jnp.asarray(lens)  # held at ROWS: 50 steps, then again
                    for _ in range(50):
                        k, v, lengths, tokens = step(p, k, v, lengths, tokens, CFG,
                                                     args.attn_len)
                tokens.block_until_ready()
                return k, v, tokens

            k, v, tokens = run(50, k, v, tokens)
            reads = []
            for _ in range(3):
                t = time.perf_counter()
                k, v, tokens = run(args.steps, k, v, tokens)
                reads.append((time.perf_counter() - t) / args.steps * 1e3)
            res[f"step_ms_fill{fill}"] = reads
            print(form, "fill", fill, "step ms", ["%.4f" % r for r in reads], flush=True)
            if fill == int(args.fill.split(",")[0]):
                tdir = os.path.join(args.out, f"trace_{form}")
                jax.profiler.start_trace(tdir)
                k, v, tokens = run(100, k, v, tokens)
                jax.profiler.stop_trace()
                ops = device_ops(tdir)
                top = sorted(ops.items(), key=lambda kv: -kv[1][1])[:45]
                res["ops_us_per_step"] = {n: [c, ns / 100 / 1e3] for n, (c, ns) in top}
                for n, (c, ns) in top:
                    print(f"   {n[:70]:70s} x{c:6d} {ns / 100 / 1e3:9.2f} us/step")
            del k, v
        del p, step
        jax.clear_caches()
    with open(os.path.join(args.out, "step_forms.json"), "w") as f:
        json.dump(results, f, indent=1)


compile_all() if args.compile else measure()
