"""`lowered_hash.py <checkout>`: a hash of the lowered text of each program
PR 56 must not move (`ci/chip_calls/pr49/lowered_hash.py` with Granite's two
programs beside the others'), at the benchmark cells' real shapes, for the described
v5e (no chip): the decode steps and prompt passes of Kimi Linear, openPangu,
EvaByte and Jamba (which share `models/hybrid.py`, `ops/moe.py` and the
decode-attention kernel's wrapper with the new model) and the dense decode
step (the same wrapper). Run it on the parent's checkout and on this one:
the lines must be the same. A Mosaic kernel's serialized body carries the
PATH of its source file, so bodies are left out of the hash (`ci/chip_calls/
pr45/lowered_hash.py` is where this comes from)."""
import dataclasses
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
from ray_tpu.ops.pallas import _util
from ray_tpu.parallel import MeshConfig, make_mesh
from ray_tpu.train.step import batch_sharding, default_optimizer, make_train_step
from perfbench.lib import (eva_model, granite_model, hybrid_model, jamba_model,
                           pangu_model)

_util.on_tpu = lambda: True
topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
one = SingleDeviceSharding(topo.devices[0])
chip = lambda dims, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(dims, dtype, sharding=one)
as_shapes = lambda tree: jax.tree_util.tree_map(lambda a: chip(a.shape, a.dtype), tree)
BODY = re.compile(r'(\\22body\\22: \\22)[A-Za-z0-9+/=]*')
out = {}


def h(name, lowered):
    text = lowered.as_text()
    out[name] = (hashlib.sha256(BODY.sub(r"\1", text).encode()).hexdigest()[:16]
                 + f" ({len(BODY.findall(text))} kernel bodies left out)")


def serving(name, mod, file, attn):
    conf = json.load(open(os.path.join(root, "perfbench", "configs", file)))
    cfg = mod.model_config(conf)
    slots, max_len = conf["run"]["num_slots"], conf["run"]["max_len"]
    params = as_shapes(jax.eval_shape(lambda k: hybrid.init_params(k, cfg),
                                      jax.random.PRNGKey(0)))
    state = as_shapes(jax.eval_shape(lambda: cfg.make_cache(slots, max_len).state))
    ints = chip((slots,), jnp.int32)
    h(name + ".decode_step", hybrid.decode_step.lower(
        params, state, ints, ints, chip((slots,), jnp.bool_), cfg, attn))
    h(name + ".prefill_first", hybrid._prefill_first.lower(
        params, chip((1, 1023), jnp.int32), chip((1,), jnp.int32), cfg))


serving("kimi", hybrid_model, "kimi-linear-48b-a3b.1of4.json", 8192)
serving("pangu", pangu_model, "openpangu-ultra-moe-718b.1of32.json", 8192)
serving("evabyte", eva_model, "evabyte-6.5b.1of4.json", 4096)
serving("jamba", jamba_model, "jamba2-3b.json", 1024)
serving("granite", granite_model, "granite-4.0-h-small.1of2.json", 16384)
cfg = ModelConfig(vocab_size=92544, d_model=2048, n_layers=24, n_heads=16,
                  n_kv_heads=8, d_ff=8192, rope_theta=1e6)
params = as_shapes(jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                                  jax.random.PRNGKey(0)))
kv = chip((cfg.n_layers, 32, cfg.n_kv_heads, 1024, cfg.head_dim))
ints = chip((32,), jnp.int32)
h("chat.decode_step_fused", decode_step_fused.lower(params, kv, kv, ints, ints, cfg, 1024))
print(json.dumps(out, indent=1))
