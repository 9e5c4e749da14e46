"""`compiled_stats.py <checkout>`: is the COMPILED program the parent's? The
list-form cells' decode step (and one prompt pass of Kimi's) at the
benchmark's real shapes, compiled for the described v5e (no chip): fusions,
Mosaic calls, `call` instructions left, FLOP and bytes accessed as XLA counts
them, temp and alias bytes. Run on the parent's checkout and on this one."""
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"
root = os.path.abspath(sys.argv[1])
sys.path.insert(0, root)
import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from ray_tpu.models import hybrid
from ray_tpu.ops.pallas import _util
from perfbench.lib import hybrid_model, pangu_model

jax.config.update("jax_enable_compilation_cache", False)
_util.on_tpu = lambda: True
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
chip = lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one)
as_shapes = lambda tree: jax.tree_util.tree_map(lambda a: chip(a.shape, a.dtype), tree)


def stats(name, lowered):
    c = lowered.compile()
    text = c.as_text()
    cost = c.cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    mem = c.memory_analysis()
    print(json.dumps({
        "program": name,
        "fusions": len(re.findall(r" fusion\(", text)),
        "mosaic_calls": text.count('custom_call_target="tpu_custom_call"'),
        "calls_left": len(re.findall(r"\s(?:async-)?call(?:-start)?\(", text)),
        "gflop": round(cost.get("flops", 0) / 1e9, 2),
        "gb_accessed": round(cost.get("bytes accessed", 0) / 1e9, 3),
        "temp_mb": round(mem.temp_size_in_bytes / 1e6, 2),
        "alias_mb": round(mem.alias_size_in_bytes / 1e6, 2)}), flush=True)


def cell(name, mod, file, attn_len, prompt):
    conf = json.load(open(os.path.join(root, "perfbench", "configs", file)))
    cfg = mod.model_config(conf)
    slots, max_len = conf["run"]["num_slots"], conf["run"]["max_len"]
    params = as_shapes(jax.eval_shape(lambda k: hybrid.init_params(k, cfg),
                                      jax.random.PRNGKey(0)))
    state = as_shapes(jax.eval_shape(lambda: cfg.make_cache(slots, max_len).state))
    ints = chip((slots,), jnp.int32)
    stats(f"{name}.decode_step attn_len {attn_len}", hybrid.decode_step.lower(
        params, state, ints, ints, chip((slots,), jnp.bool_), cfg, attn_len))
    if prompt:
        stats(f"{name}._prefill_first {prompt[0]}x{prompt[1]}",
              hybrid._prefill_first.lower(params, chip(prompt, jnp.int32),
                                          chip(prompt[:1], jnp.int32), cfg))


cell("kimi", hybrid_model, "kimi-linear-48b-a3b.1of4.json", 512, (4, 1024))
cell("pangu", pangu_model, "openpangu-ultra-moe-718b.1of32.json", 2048, None)
