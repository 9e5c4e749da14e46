"""The dense decode step and the attention kernel alone: the grid form (the
parent's `decode_attention.py`, read from a `git archive` of the parent
commit under `--parent`) beside the walk this tree holds, at block heights
of 128 / 256 / 512 rows.

default: each form's step timed on the chip this process holds, at the chat
cell's shapes (InternLM2-1.8B, 32 slots x 1024, random bf16 weights), under
each bucket of `--buckets` and each fill of `--fills` (`busy:rows`; rows are
cut to the bucket). `--alone`: the kernel alone, one call a layer in a scan,
at the chat cell's and Jamba's shapes, the two forms' outputs compared bit
for bit.

The program holds ONE form; the grid lives in the parent's tree.
"""
import argparse
import importlib.util
import json
import os
import sys
import time
import types

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "../../..")))
ap = argparse.ArgumentParser()
ap.add_argument("--parent", default="_check/parent")
ap.add_argument("--variants", default="_check/variants",
                help="a form `x_<name>` is <this directory>/<name>.py, an earlier spelling of the walk")
ap.add_argument("--forms", default="grid,walk256,walk128,walk512")
ap.add_argument("--buckets", default="64,128,256,512,1024")
ap.add_argument("--fills", default="4:330,32:330,8:330,23:330,32:1023")
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--alone", action="store_true")
ap.add_argument("--tiny", action="store_true", help="a rehearsal on the CPU: toy shapes, interpret mode")
ap.add_argument("--out", default="chiprun_out/pr45")
args = ap.parse_args()

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.models import ModelConfig, serving
from ray_tpu.models.transformer import init_params
from ray_tpu.ops.pallas import decode_attention as walk

CFG = ModelConfig(vocab_size=92544, d_model=2048, n_layers=24, n_heads=16,
                  n_kv_heads=8, d_ff=8192, rope_theta=1e6)
SLOTS, MAX_LEN = 32, 1024
if args.tiny:
    CFG, SLOTS, MAX_LEN = ModelConfig.tiny(), 4, 128
    serving.decode_attention.uses_decode_kernel = lambda *a: True



def load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


grid = load("parent_decode_attention",
            os.path.join(args.parent, "ray_tpu/ops/pallas/decode_attention.py"))
# the parent's kernel under the names the step calls
GRID = types.SimpleNamespace(uses_decode_kernel=grid.uses_decode_kernel,
                             live_items=grid.live_blocks,
                             gqa_decode_attention=grid.gqa_decode_attention)


def kernel_of(form):
    """(module the step calls, block height) of `form`; sets the height."""
    if form == "grid":
        return GRID
    if form.startswith("x_"):
        return load(form, os.path.join(args.variants, form[2:] + ".py"))
    walk._BLOCK_ROWS = int(form[4:])
    return walk


def step_of(form):
    """`decode_step_fused`'s own text around `form`'s attention. A function
    of its own a form: jax keeps traces by the function's identity."""
    def step(params, k_all, v_all, lengths, tokens, cfg, attn_len):
        serving.decode_attention = kernel_of(form)
        return serving.decode_step_fused.__wrapped__(
            params, k_all, v_all, lengths, tokens, cfg, attn_len)

    return jax.jit(step, static_argnames=("cfg", "attn_len"),
                   donate_argnums=(1, 2, 3))


def fills(attn_len):
    for f in args.fills.split(","):
        busy, rows = map(int, f.split(":"))
        yield f, busy, min(rows, attn_len - 1)


def measure_steps():
    params = jax.jit(lambda k: init_params(k, CFG))(jax.random.PRNGKey(7))
    shape = (CFG.n_layers, SLOTS, CFG.n_kv_heads, MAX_LEN, CFG.head_dim)
    results = {}
    for attn_len in map(int, args.buckets.split(",")):
        for form in args.forms.split(","):
            if form.startswith("walk") and int(form[4:]) > attn_len and form != "walk256":
                continue  # the same program as walk256 under this bucket
            step = step_of(form)
            for name, busy, rows in fills(attn_len):
                k = jnp.zeros(shape, CFG.dtype) + 0.01
                v = jnp.zeros(shape, CFG.dtype) + 0.01
                lens = np.zeros(SLOTS, np.int32)
                lens[:busy] = rows
                tokens = jnp.arange(SLOTS, dtype=jnp.int32) + 5

                def run(n, k, v, tokens, lens=lens):
                    for _ in range(n // 50):
                        lengths = jnp.asarray(lens)  # held: 50 steps, then again
                        for _ in range(50):
                            k, v, lengths, tokens = step(params, k, v, lengths,
                                                         tokens, CFG, attn_len)
                    tokens.block_until_ready()
                    return k, v, tokens

                k, v, tokens = run(50, k, v, tokens)
                reads = []
                for _ in range(3):
                    t = time.perf_counter()
                    k, v, tokens = run(args.steps, k, v, tokens)
                    reads.append((time.perf_counter() - t) / args.steps * 1e3)
                results[f"{attn_len}/{form}/{name}"] = reads
                print(attn_len, form, name, "step ms",
                      ["%.4f" % r for r in reads], flush=True)
                del k, v
            del step
            jax.clear_caches()
    return results


def measure_alone():
    """One call a layer in a scan over the layers, as the steps make it."""
    results = {}
    if args.tiny:
        return {}
    shapes = {"chat": (24, 32, 8, 2, 1024, ((4, 330), (32, 330), (32, 1023))),
              "jamba": (2, 256, 1, 20, 1024, ((63, 330), (256, 1023)))}
    for name, (L, B, kvh, rep, max_len, cases) in shapes.items():
        ks = jax.random.split(jax.random.PRNGKey(3), 5)
        normal = lambda k, dims: jax.random.normal(k, dims, jnp.float32).astype(jnp.bfloat16)
        q, kc, vc = (normal(ks[0], (B, kvh, rep, 128)), normal(ks[1], (B, kvh, 128)),
                     normal(ks[2], (B, kvh, 128)))
        k_all = normal(ks[3], (L, B, kvh, max_len, 128))
        v_all = normal(ks[4], (L, B, kvh, max_len, 128))
        for attn_len in (64, 256, 512, 1024):
            for busy, rows in cases:
                rows = min(rows, attn_len - 1)
                lens = np.zeros(B, np.int32)
                lens[np.random.default_rng(0).permutation(B)[:busy]] = rows
                lengths = jnp.asarray(lens)
                outs = {}
                for form in args.forms.split(","):
                    if form.startswith("walk") and int(form[4:]) > attn_len and form != "walk256":
                        continue
                    mod = kernel_of(form)

                    def layers(q, kc, vc, k_all, v_all, lengths, mod=mod):
                        items = mod.live_items(lengths, attn_len)

                        def body(_, layer):
                            return None, mod.gqa_decode_attention(
                                q, kc, vc, k_all, v_all, layer, items, attn_len)

                        return jax.lax.scan(body, None, jnp.arange(L))[1]

                    f = jax.jit(layers)
                    out = f(q, kc, vc, k_all, v_all, lengths).block_until_ready()
                    reads = []
                    for _ in range(3):
                        t = time.perf_counter()
                        for _ in range(200):
                            out = f(q, kc, vc, k_all, v_all, lengths)
                        out.block_until_ready()
                        reads.append((time.perf_counter() - t) / 200 / L * 1e6)
                    outs[form] = np.asarray(out.astype(jnp.float32))
                    same = bool(np.array_equal(outs[form], outs["grid"])) \
                        if "grid" in outs else None
                    results[f"{name}/{attn_len}/{busy}x{rows}/{form}"] = {
                        "us_per_call": reads, "same_bits_as_grid": same}
                    print(name, attn_len, f"{busy}x{rows}", form, "us/call",
                          ["%.2f" % r for r in reads], "same bits:", same, flush=True)
    return results


os.makedirs(args.out, exist_ok=True)
dev = jax.devices()[0]
assert dev.platform == "tpu" or args.tiny, dev
what = "alone" if args.alone else "step_forms"
with open(os.path.join(args.out, what + ".json"), "w") as f:
    json.dump({"device_kind": dev.device_kind,
               what: measure_alone() if args.alone else measure_steps()}, f, indent=1)
