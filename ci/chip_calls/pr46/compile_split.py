"""`compile_split.py <checkout>`: ONE fresh process that holds the four chips
builds the four-chip cell's train step from `<checkout>`'s program, lowers
and compiles it once (a hit in jax's persistent cache after a run of that
tree's cell) and prints jax's own three durations apart: tracing, lowering,
and the backend's compile (here: the cache's read). The cell's `compile.s`
is their sum, over the step and the state's initialiser. No state is made:
shapes with the cell's shardings stand for it.
"""
import collections
import json
import os
import sys
import time

root = os.path.abspath(sys.argv[1])
sys.path.insert(0, root)
os.chdir(root)
from perfbench.lib.manifest import prepare_env  # noqa: E402

prepare_env(root, False)  # the cache directory the cell's runs use
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import monitoring  # noqa: E402

from perfbench.lib import model  # noqa: E402
from ray_tpu.parallel import MeshConfig, make_mesh  # noqa: E402
from ray_tpu.train import batch_sharding, make_train_step  # noqa: E402
from ray_tpu.train.step import default_optimizer  # noqa: E402

conf = json.load(open("perfbench/configs/mistral-7b-v0.3.4chip.json"))
tr = json.load(open("perfbench/traffic/pretrain-2x2048.json"))
run = conf["run"]
cfg = model.model_config(conf, n_layers=conf["num_hidden_layers"],
                         max_seq_len=tr["seq"], remat=run["remat"], loss_chunk=0,
                         fused_ffn=False, fused_attn=False)
mesh = make_mesh(MeshConfig(**run["mesh"]), jax.devices()[:4])
secs = collections.defaultdict(float)
hits = collections.Counter()
monitoring.register_event_duration_secs_listener(
    lambda name, s, **_: secs.__setitem__(name.split("/")[-1], secs[name.split("/")[-1]] + s))
monitoring.register_event_listener(lambda name, **_: hits.update([name.split("/")[-1]]))
step_fn, init_fn, sh = make_train_step(cfg, mesh, default_optimizer())
state = jax.tree_util.tree_map(
    lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
    jax.eval_shape(init_fn, jax.random.PRNGKey(0)), sh)
b_sh = batch_sharding(mesh)
batch = {k: jax.ShapeDtypeStruct((tr["batch"], tr["seq"]), jnp.int32, sharding=b_sh[k])
         for k in ("inputs", "targets")}
secs.clear()
t0 = time.perf_counter()
lowered = step_fn.lower(state, batch)
t1 = time.perf_counter()
lowered.compile()
t2 = time.perf_counter()
print(json.dumps({
    "tree": sys.argv[1], "device_kind": jax.devices()[0].device_kind,
    "lower_wall_s": round(t1 - t0, 3), "compile_wall_s": round(t2 - t1, 3),
    "trace_s": round(secs["jaxpr_trace_duration"], 3),
    "to_mlir_s": round(secs["jaxpr_to_mlir_module_duration"], 3),
    "backend_compile_s": round(secs["backend_compile_duration"], 3),
    "cache": {k: v for k, v in hits.items() if "cache" in k},
    "lowered_text_bytes": len(lowered.as_text())}))
