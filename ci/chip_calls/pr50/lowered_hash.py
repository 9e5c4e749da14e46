"""`lowered_hash.py <checkout>`: a hash of the lowered text of each program
PR 50 must not move, at the benchmark cells' real shapes, for the described
v5e (no chip): the chat cell's dense decode step and prompt pass, both
training cells' step (two layers) and the decode step and prompt pass of the
three runs-form cells (Jamba, EvaByte, Granite), which share
`models/hybrid.py` with the list form. Run it on the parent's checkout and on
this one: the lines must be the same. A Mosaic kernel's serialized body
carries the PATH of its source file, so bodies are left out of the hash
(their source files are compared by `git diff`); the programs that DO change,
the two list-form cells', are listed last, for contrast."""
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
from ray_tpu.models.serving import decode_step_fused, prefill_slots
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


def train(name, chips, mesh, fused):
    cfg = dataclasses.replace(
        ModelConfig(vocab_size=32768, d_model=4096, n_layers=2, n_heads=32,
                    n_kv_heads=8, d_ff=14336, rope_theta=1e6),
        max_seq_len=2048, remat="dots", loss_chunk=0, fused_ffn=fused, fused_attn=fused)
    mesh = make_mesh(MeshConfig(**mesh), topo.devices[:chips])
    step_fn, init_fn, shardings = make_train_step(cfg, mesh, default_optimizer())
    state = jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        jax.eval_shape(init_fn, jax.random.PRNGKey(0)), shardings)
    b_sh = batch_sharding(mesh)
    batch = {k: jax.ShapeDtypeStruct((2, 2048), jnp.int32, sharding=b_sh[k])
             for k in ("inputs", "targets")}
    h(name, step_fn.lower(state, batch))


cfg = ModelConfig(vocab_size=92544, d_model=2048, n_layers=24, n_heads=16,
                  n_kv_heads=8, d_ff=8192, rope_theta=1e6)
params = as_shapes(jax.eval_shape(lambda k: transformer.init_params(k, cfg),
                                  jax.random.PRNGKey(0)))
kv = chip((cfg.n_layers, 32, cfg.n_kv_heads, 1024, cfg.head_dim))
ints = chip((32,), jnp.int32)
h("chat.decode_step_fused", decode_step_fused.lower(params, kv, kv, ints, ints, cfg, 1024))
h("chat.prefill_slots", prefill_slots.lower(
    params, chip((4, 256), jnp.int32), chip((4,), jnp.int32), cfg, 1024))
train("mistral7b-train-1chip.step(2 layers)", 1, {"dp": 1}, True)
train("mistral7b-train-4chip.step(2 layers)", 4, {"dp": 1, "fsdp": 2, "tp": 2}, False)
serving("jamba", jamba_model, "jamba2-3b.json", 1024)
serving("evabyte", eva_model, "evabyte-6.5b.1of4.json", 4096)
serving("granite", granite_model, "granite-4.0-h-small.1of2.json", 4096)
# the ones that change
serving("kimi(changes)", hybrid_model, "kimi-linear-48b-a3b.1of4.json", 8192)
serving("pangu(changes)", pangu_model, "openpangu-ultra-moe-718b.1of32.json", 8192)
print(json.dumps(out, indent=1))
