"""`trace_lower.py <checkout> [kimi|pangu] [all]`: what the host pays before
XLA sees a program, for the list-form cells' engine programs at the
benchmark's real shapes, abstract arguments, the described v5e (no chip):
`jit.trace` then `.lower` of each, in the order the replica warms them, with
the size of the lowered text and how many private functions it holds.
Without `all`: three decode steps and three prompt passes (ISSUE 50's six).
Seconds here are this sandbox's CPU: a sizing, never a device metric."""
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"
root = os.path.abspath(sys.argv[1])
which = sys.argv[2] if len(sys.argv) > 2 else "kimi"
everything = "all" in sys.argv[3:]
sys.path.insert(0, root)
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import hybrid
from ray_tpu.ops.pallas import _util
from perfbench.lib import hybrid_model, pangu_model

_util.on_tpu = lambda: True
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
chip = lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one)
as_shapes = lambda tree: jax.tree_util.tree_map(lambda a: chip(a.shape, a.dtype), tree)

mod, file, traffic = {
    "kimi": (hybrid_model, "kimi-linear-48b-a3b.1of4.json", "reasoning-open-loop.json"),
    "pangu": (pangu_model, "openpangu-ultra-moe-718b.1of32.json", "longctx-open-loop.json"),
}[which]
conf = json.load(open(os.path.join(root, "perfbench", "configs", file)))
warm = json.load(open(os.path.join(root, "perfbench", "traffic", traffic)))["warm"]
cfg = mod.model_config(conf)
slots, max_len = conf["run"]["num_slots"], conf["run"]["max_len"]
params = as_shapes(jax.eval_shape(lambda k: hybrid.init_params(k, cfg),
                                  jax.random.PRNGKey(0)))
cache = jax.eval_shape(lambda: cfg.make_cache(slots, max_len).state)
state = as_shapes(cache)
ints = chip((slots,), jnp.int32)
most = lambda bucket: max(1, min(4, cfg.prefill_tokens // bucket))

programs = []
for bucket in warm["prefill_buckets"]:
    for nb in warm["admission_batches"]:
        if nb <= most(bucket):
            programs.append((f"prefill {nb}x{bucket}", hybrid._prefill_first,
                             (params, chip((nb, bucket), jnp.int32),
                              chip((nb,), jnp.int32), cfg)))
for attn_len in warm["attention_buckets"]:
    programs.append((f"decode {attn_len}", hybrid.decode_step,
                     (params, state, ints, ints, chip((slots,), jnp.bool_), cfg,
                      attn_len)))
if not everything:
    pick = lambda kind: [p for p in programs if p[0].startswith(kind)]
    d, p = pick("decode"), pick("prefill")
    programs = d[:3] + [p[0], p[len(p) // 2], p[-1]]

total = {"trace": 0.0, "lower": 0.0}
for name, fn, args in programs:
    t0 = time.perf_counter()
    traced = fn.trace(*args)
    t1 = time.perf_counter()
    lowered = traced.lower()
    t2 = time.perf_counter()
    text = lowered.as_text()
    private = len(re.findall(r"func\.func private", text))
    total["trace"] += t1 - t0
    total["lower"] += t2 - t1
    print(f"{name:18s} trace {t1 - t0:6.2f} s  lower {t2 - t1:6.2f} s  "
          f"text {len(text) / 1e3:7.0f} kB  private functions {private}", flush=True)
print(f"{which}: {len(programs)} programs, trace {total['trace']:.2f} s, "
      f"lower {total['lower']:.2f} s, sum {sum(total.values()):.2f} s")
