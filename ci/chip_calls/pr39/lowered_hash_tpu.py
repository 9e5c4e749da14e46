import os, sys, hashlib, json
os.environ.setdefault("TPU_LOG_DIR", "disabled"); os.environ["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"
root = sys.argv[1]
sys.path.insert(0, root)
import jax, jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding
from ray_tpu.ops.pallas import _util
_util.on_tpu = lambda: True
from ray_tpu.models import hybrid, transformer
from ray_tpu.models.transformer import ModelConfig
from ray_tpu.models.serving import decode_step_fused
from perfbench.lib import jamba_model, pangu_model, hybrid_model
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
chip = lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one)
as_shapes = lambda tree: jax.tree_util.tree_map(lambda a: chip(a.shape, a.dtype), tree)
out = {}
import re
BODY = re.compile(r'(\\22body\\22: \\22)[A-Za-z0-9+/=]*')
def h(name, lowered):
    text = lowered.as_text()
    bodies = len(BODY.findall(text))
    # a Mosaic kernel's serialized body carries the PATH of its source file
    out[name] = hashlib.sha256(BODY.sub(r"\1", text).encode()).hexdigest()[:16] + f" ({bodies} kernel bodies left out)"
for name, mod, file, attn in (("jamba", jamba_model, "jamba2-3b.json", 1024), ("pangu", pangu_model, "openpangu-ultra-moe-718b.1of32.json", 8192), ("kimi", hybrid_model, "kimi-linear-48b-a3b.1of4.json", 8192)):
    conf = json.load(open(os.path.join(root, "perfbench", "configs", file)))
    cfg = mod.model_config(conf)
    slots, max_len = conf["run"]["num_slots"], conf["run"]["max_len"]
    params = as_shapes(jax.eval_shape(lambda k: hybrid.init_params(k, cfg), jax.random.PRNGKey(0)))
    state = as_shapes(jax.eval_shape(lambda: cfg.make_cache(slots, max_len).state))
    ints = chip((slots,), jnp.int32)
    h(name + ".decode_step", hybrid.decode_step.lower(params, state, ints, ints, chip((slots,), jnp.bool_), cfg, attn))
    h(name + ".prefill_first", hybrid._prefill_first.lower(params, chip((1, 1023), jnp.int32), chip((1,), jnp.int32), cfg))
B1 = ModelConfig.b1()
params = as_shapes(jax.eval_shape(lambda k: transformer.init_params(k, B1), jax.random.PRNGKey(0)))
kv = chip((B1.n_layers, 32, B1.n_kv_heads, 1024, B1.head_dim))
ints = chip((32,), jnp.int32)
h("dense.decode_step_fused", decode_step_fused.lower(params, kv, kv, ints, ints, B1, 512))
print(json.dumps(out, indent=1))
