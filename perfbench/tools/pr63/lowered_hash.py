"""`lowered_hash.py <checkout>`: a hash of each program PR 63 must not move
(`perfbench/tools/pr60/lowered_hash.py` with Command A+'s step and passes
beside the others'), at the benchmark cells' real shapes, for the described
v5e (no chip): the decode steps and prompt passes of Kimi Linear, openPangu,
EvaByte, Jamba, Granite, Keye and Command A+ (which share `models/hybrid.py`
and `ops/moe.py` with the new model; Command A+ the window form's cache,
walk and ring, whose block became the configuration's in this PR: its step
at 12 slots x 49,152 and its prompt passes inside one window, 1 x 1,024 and
1 x 4,096, and walked, 1 x 49,152) and the dense decode step, as lowered
text with the Mosaic kernels' serialized bodies left out (a body carries the
PATH and LINE of its source); the flash kernels and the decode kernel as
JAXPRS, as PR 60 compared them (this PR edits neither). Run it on the
parent's checkout and on this one: the lines must be the same."""
import hashlib
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

from ray_tpu.models import hybrid, transformer
from ray_tpu.models.serving import decode_step_fused
from ray_tpu.models.transformer import ModelConfig
from ray_tpu.ops.attention import attention
from ray_tpu.ops.cache import write_rows
from ray_tpu.ops.pallas import _util, decode_attention
from perfbench.lib import (cmda_model, eva_model, granite_model, hybrid_model,
                           jamba_model, keye_model, pangu_model)

_util.on_tpu = lambda: True
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
chip = lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one)
as_shapes = lambda tree: jax.tree_util.tree_map(lambda a: chip(a.shape, a.dtype), tree)
BODY = re.compile(r'(\\22body\\22: \\22)[A-Za-z0-9+/=]*')
WHERE = re.compile(r" at [^ \n]*\.py:\d+")
out = {}


def h(name, lowered):
    text = lowered.as_text()
    out[name] = (hashlib.sha256(BODY.sub(r"\1", text).encode()).hexdigest()[:16]
                 + f" ({len(BODY.findall(text))} kernel bodies left out)")


def j(name, fn, *shapes):
    text = WHERE.sub("", str(jax.make_jaxpr(fn)(*shapes)))
    out[name] = hashlib.sha256(text.encode()).hexdigest()[:16] + \
        f" (jaxpr, {text.count('pallas_call')} kernels inside)"


def serving(name, mod, file, attn, buckets=(1024,)):
    conf = json.load(open(os.path.join(root, "perfbench", "configs", file)))
    cfg = mod.model_config(conf)
    slots, max_len = conf["run"]["num_slots"], conf["run"]["max_len"]
    params = as_shapes(jax.eval_shape(lambda k: hybrid.init_params(k, cfg),
                                      jax.random.PRNGKey(0)))
    state = as_shapes(jax.eval_shape(lambda: cfg.make_cache(slots, max_len).state))
    ints = chip((slots,), jnp.int32)
    h(name + ".decode_step", hybrid.decode_step.lower(
        params, state, ints, ints, chip((slots,), jnp.bool_), cfg, attn))
    for bucket in buckets:
        h(name + ".prefill_first" + (f".{bucket}" if len(buckets) > 1 else ""),
          hybrid._prefill_first.lower(
              params, chip((1, bucket), jnp.int32), chip((1,), jnp.int32), cfg))


serving("kimi", hybrid_model, "kimi-linear-48b-a3b.1of4.json", 8192)
serving("pangu", pangu_model, "openpangu-ultra-moe-718b.1of32.json", 8192)
serving("evabyte", eva_model, "evabyte-6.5b.1of4.json", 4096)
serving("jamba", jamba_model, "jamba2-3b.json", 1024)
serving("granite", granite_model, "granite-4.0-h-small.1of2.json", 16384)
serving("keye", keye_model, "keye-vl-2.0-30b-a3b.1of8.json", 16384)
serving("cmda", cmda_model, "command-a-plus-05-2026.1of8.json", 49152,
        (1024, 4096, 49152))
cfg = ModelConfig(vocab_size=92544, d_model=2048, n_layers=24, n_heads=16,
                  n_kv_heads=8, d_ff=8192, rope_theta=1e6)
params = as_shapes(jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                                  jax.random.PRNGKey(0)))
kv = chip((cfg.n_layers, 32, cfg.n_kv_heads, 1024, cfg.head_dim))
ints = chip((32,), jnp.int32)
h("chat.decode_step_fused", decode_step_fused.lower(params, kv, kv, ints, ints, cfg, 1024))

# the kernels' bodies, as jaxprs: Mistral-7B's attention (32 heads on 8, 2 x
# 2048 x 128), loss and gradient; the chat cell's decode kernel and row write
q, k = chip((2, 32, 2048, 128)), chip((2, 8, 2048, 128))
j("mistral.attention.fwd", lambda q, k, v: attention(q, k, v), q, k, k)
j("mistral.attention.grad", jax.grad(
    lambda q, k, v: jnp.sum(attention(q, k, v).astype(jnp.float32)), argnums=(0, 1, 2)),
  q, k, k)
j("chat.gqa_decode_attention", lambda q, kc, vc, ka, va, n: decode_attention.gqa_decode_attention(
    q, kc, vc, ka, va, jnp.asarray(3), decode_attention.live_items(n, 1024), 1024),
  chip((32, 8, 2, 128)), chip((32, 8, 128)), chip((32, 8, 128)), kv, kv, ints)
j("chat.write_rows", lambda c, r, n: write_rows(c, r, n), kv, chip((24, 32, 8, 128)), ints)
print(json.dumps(out, indent=1))
