"""`trace_cost.py <checkout> <out.json>`: what tracing and lowering the
four-chip cell's step costs on THIS host, the chip untouched (the CPU's four
virtual devices stand for the mesh; the attention is then the plain one, on
both trees alike): wall seconds of `step_fn.lower` eight times over, each
from cleared caches, and one profiled pass whose functions' own time is
written to `<out.json>` (keyed by file:line:name, paths relative to the
checkout) for a diff against the other tree's."""
import cProfile
import dataclasses
import json
import os
import pstats
import statistics
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"
root = os.path.abspath(sys.argv[1])
sys.path.insert(0, root)
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models.transformer import ModelConfig  # noqa: E402
from ray_tpu.parallel import MeshConfig, make_mesh  # noqa: E402
from ray_tpu.train.step import batch_sharding, default_optimizer, make_train_step  # noqa: E402

cfg = dataclasses.replace(
    ModelConfig(vocab_size=32768, d_model=4096, n_layers=22, n_heads=32,
                n_kv_heads=8, d_ff=14336, rope_theta=1e6),
    max_seq_len=2048, remat="dots", loss_chunk=0)
mesh = make_mesh(MeshConfig(dp=1, fsdp=2, tp=2), jax.devices()[:4])


def program():
    step_fn, init_fn, sh = make_train_step(cfg, mesh, default_optimizer())
    state = jax.tree_util.tree_map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        jax.eval_shape(init_fn, jax.random.PRNGKey(0)), sh)
    b_sh = batch_sharding(mesh)
    batch = {k: jax.ShapeDtypeStruct((2, 2048), jnp.int32, sharding=b_sh[k])
             for k in ("inputs", "targets")}
    return step_fn, state, batch


walls = []
for _ in range(8):
    step_fn, state, batch = program()
    t = time.perf_counter()
    step_fn.lower(state, batch)
    walls.append(time.perf_counter() - t)
    jax.clear_caches()
step_fn, state, batch = program()
prof = cProfile.Profile()
prof.enable()
step_fn.lower(state, batch)
prof.disable()
stats = pstats.Stats(prof)
own = {f"{f.replace(root, '')}:{line}:{name}": [calls, round(tot, 5)]
       for (f, line, name), (_, calls, tot, _, _) in stats.stats.items()}
with open(sys.argv[2], "w") as f:
    json.dump({"total_calls": stats.total_calls, "own": own}, f)
print(json.dumps({"tree": sys.argv[1], "first_s": round(walls[0], 3),
                  "min_s": round(min(walls[1:]), 3),
                  "median_s": round(statistics.median(walls[1:]), 3),
                  "total_calls": stats.total_calls}))
