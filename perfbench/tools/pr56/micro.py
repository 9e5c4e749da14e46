"""PR 56, on the chip, ONE process: the Keye cell's programs alone (no Serve,
no engine): prompt passes at three buckets, decode steps at given slot
lengths, with the kernels and with the forms XLA runs in their place, one
against the other; a short trace reduced to the device's top operations.

    python3 perfbench/tools/pr56/micro.py [what ...]   # what: pass step xla trace
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)
OUT = os.path.join(ROOT, "chiprun_out", "pr56")
os.makedirs(OUT, exist_ok=True)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from perfbench.lib import keye_model, xplane  # noqa: E402
from ray_tpu.models import hybrid  # noqa: E402
from ray_tpu.ops.pallas import dsa as kernels  # noqa: E402

WHAT = sys.argv[1:] or ["pass", "step", "xla", "trace"]
conf = json.load(open(os.path.join(ROOT, "perfbench/configs/keye-vl-2.0-30b-a3b.1of8.json")))
cfg = keye_model.model_config(conf)
B, MAX = conf["run"]["num_slots"], conf["run"]["max_len"]
out = {"device": str(jax.devices()[0].device_kind)}
t0 = time.time()
params = keye_model.make_params(cfg, 2147480123)
jax.block_until_ready(params)
print(f"weights made in {time.time() - t0:.1f} s", flush=True)
cache = cfg.make_cache(B, MAX)
rng = np.random.default_rng(0)


def timed(fn, n=1):
    t = time.perf_counter()
    for _ in range(n):
        r = fn()
    jax.block_until_ready(r)
    return (time.perf_counter() - t) / n, r


def admit(slot, n):
    bucket = cache.prompt_bucket(n)
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = rng.integers(0, cfg.vocab_size, n)
    lens = jnp.asarray([n], jnp.int32)
    first, rows = cache.prefill(params, jnp.asarray(toks), lens)
    return cache.write(STATE["L"], STATE["T"], jnp.asarray([slot], jnp.int32), rows, lens, first)


STATE = {"L": jnp.zeros((B,), jnp.int32), "T": jnp.zeros((B,), jnp.int32)}

if "pass" in WHAT:
    for bucket in (8192, 16384, 32768):
        toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, bucket)), jnp.int32)
        lens = jnp.asarray([bucket - 7], jnp.int32)
        cold, _ = timed(lambda: cache.prefill(params, toks, lens))
        warm, _ = timed(lambda: cache.prefill(params, toks, lens), 2)
        out[f"pass_{bucket}_s"] = {"cold": cold, "warm": warm,
                                   "us_per_token": 1e6 * warm / bucket}
        print(f"pass {bucket}: cold {cold:.2f} s, warm {warm:.4f} s = "
              f"{1e6 * warm / bucket:.2f} us a token", flush=True)


def steps(lengths, attn_len, n=20, tag=""):
    for slot, ln in enumerate(lengths):
        if ln:
            STATE["L"], STATE["T"] = admit(slot, ln)
    L0, T0 = STATE["L"], STATE["T"]

    def run():
        L, T = L0, T0
        for _ in range(n):
            L, T, rep = cache.decode(params, L, T, attn_len, {})
        return rep
    cold, _ = timed(lambda: cache.decode(params, L0 + 0, T0 + 0, attn_len, {})[2])
    warm, _ = timed(run)
    warm /= n
    print(f"step {tag} lengths {lengths} under {attn_len}: cold {cold:.2f} s, "
          f"{1e3 * warm:.3f} ms a step", flush=True)
    out[f"step_{tag}_{attn_len}_{sum(1 for x in lengths if x)}busy_ms"] = 1e3 * warm
    STATE["L"], STATE["T"] = jnp.zeros((B,), jnp.int32), jnp.zeros((B,), jnp.int32)


MIX3 = [14000, 0, 9000, 0, 0, 16000, 0, 0]
MIX8 = [14000, 30000, 9000, 20000, 8200, 16000, 25000, 12000]
if "step" in WHAT:
    steps(MIX3, 16384, tag="kernels")
    steps(MIX3, 32768, tag="kernels")
    steps(MIX8, 32768, tag="kernels")
    steps([0] * 8, 16384, tag="kernels_idle")

if "xla" in WHAT:
    # the forms XLA runs in the kernels' place, one kernel at a time
    for name in ("uses_rows_kernel", "uses_scores_kernel"):
        was = getattr(kernels, name)
        setattr(kernels, name, lambda *a: False)
        jax.clear_caches()
        steps(MIX3, 16384, tag=f"no_{name}")
        steps(MIX8, 32768, tag=f"no_{name}")
        setattr(kernels, name, was)
    jax.clear_caches()
    # the kernels against XLA's forms on the same state: logits of one step,
    # and the logits of one prompt pass at 8192
    for slot, ln in enumerate(MIX3):
        if ln:
            STATE["L"], STATE["T"] = admit(slot, ln)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (B,)), jnp.int32)
    keep = jax.tree_util.tree_map(jnp.copy, cache.state)
    _, a, _, lists_a = hybrid.decode_logits(params, cache.state, STATE["L"], toks, None, cfg, 16384)
    cache.state = keep
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8192)), jnp.int32)
    lens = jnp.asarray([8100], jnp.int32)
    pa, rows_a = hybrid.prefill(params, prompt, lens, cfg, with_routing=True)
    chosen_a = np.asarray(rows_a["chosen"])
    route_a = np.asarray(rows_a["routing"])
    del rows_a
    from ray_tpu.ops import dsa
    kernels.uses_rows_kernel = kernels.uses_scores_kernel = lambda *a: False
    dsa.uses_prompt_kernels = lambda *a: False
    jax.clear_caches()
    _, b, _, lists_b = hybrid.decode_logits(params, cache.state, STATE["L"], toks, None, cfg, 16384)
    busy = np.asarray(STATE["L"]) > 0
    rel = lambda x, y: float(jnp.linalg.norm((x - y).ravel()) / jnp.linalg.norm(y.ravel()))
    out["step_logits_kernels_vs_xla"] = rel(a[busy], b[busy])
    same = [set(np.asarray(lists_a[0])[l, s, :int(lists_a[1][l, s])])
            == set(np.asarray(lists_b[0])[l, s, :int(lists_b[1][l, s])])
            for l in range(cfg.n_layers) for s in np.nonzero(busy)[0]]
    out["step_lists_equal"] = f"{sum(same)} of {len(same)}"
    print("step: kernels vs XLA rel", out["step_logits_kernels_vs_xla"],
          "lists equal", out["step_lists_equal"], flush=True)
    pb, rows_b = hybrid.prefill(params, prompt, lens, cfg, with_routing=True)
    out["pass_logits_kernels_vs_xla"] = rel(pa, pb)
    chosen_b = np.asarray(rows_b["chosen"])
    n = 8100
    tri = np.tril(np.ones((8192, 8192), bool))
    unpack = lambda w: ((w.view(np.uint32)[np.arange(8192) // 32]
                         >> (np.arange(8192) % 32)[:, None].astype(np.uint32)) & 1
                        ).astype(bool) & tri
    diffs = []
    for l in range(cfg.n_layers):
        ua, ub = unpack(chosen_a[l, 0])[:n], unpack(chosen_b[l, 0])[:n]
        counts = ua.sum(1)
        diffs.append({"layer": l, "rows_differ": int((ua != ub).sum()),
                      "counts_ok": bool((counts == np.minimum(np.arange(n) + 1, 2048)).all())})
    out["pass_chosen"] = diffs
    out["pass_routing_differs"] = int((route_a != np.asarray(rows_b["routing"])).sum())
    print("pass: kernels vs XLA rel", out["pass_logits_kernels_vs_xla"], diffs,
          "routing differs", out["pass_routing_differs"], flush=True)
    sys.stdout.flush()
    json.dump(out, open(os.path.join(OUT, "micro.json"), "w"), indent=1)
    os._exit(0)   # the caches were cleared under the kernels: stop here

if "trace" in WHAT:
    for slot, ln in enumerate(MIX3):
        if ln:
            STATE["L"], STATE["T"] = admit(slot, ln)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 16384)), jnp.int32)
    lens = jnp.asarray([16000], jnp.int32)
    jax.block_until_ready(cache.prefill(params, toks, lens))
    L, T = STATE["L"], STATE["T"]
    L, T, rep = cache.decode(params, L, T, 16384, {})
    jax.block_until_ready(rep)
    tdir = os.path.join(OUT, "trace")
    jax.profiler.start_trace(tdir)
    for _ in range(5):
        L, T, rep = cache.decode(params, L, T, 16384, {})
    jax.block_until_ready(rep)
    jax.block_until_ready(cache.prefill(params, toks, lens))
    jax.profiler.stop_trace()
    red = xplane.reduce(xplane.load(xplane.find_xplane(tdir)), top=40)
    out["trace"] = {"busy_s": red["busy_s"], "window_s": red["window_s"],
                    "device_ops": red["device_ops"]}
    for op in red["device_ops"][:40]:
        print(op, flush=True)
    import shutil
    shutil.rmtree(tdir, ignore_errors=True)

json.dump(out, open(os.path.join(OUT, "micro.json"), "w"), indent=1)
print(json.dumps({k: v for k, v in out.items() if k != "trace"}), flush=True)
