import sys, hashlib, json
root = sys.argv[1]
sys.path.insert(0, root)
import jax, jax.numpy as jnp
from ray_tpu.models import hybrid
from ray_tpu.models.hybrid import HybridConfig
from ray_tpu.models.serving import decode_step_fused, prefill_slots, _write_slots
from ray_tpu.models.transformer import ModelConfig
from ray_tpu.models import transformer
out = {}
def h(name, lowered):
    out[name] = hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
sds = lambda t: jax.tree_util.tree_map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
# the runs form (Jamba's kind), tiny
for name, cfg in (("runs", HybridConfig.tiny_runs()), ("hybrid", HybridConfig.tiny_hybrid()), ("rotary", HybridConfig.tiny_rotary())):
    params = sds(jax.eval_shape(lambda k: hybrid.init_params(k, cfg), jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: cfg.make_cache(4, 64).state)
    ints = jax.ShapeDtypeStruct((4,), jnp.int32)
    h(name + ".decode_step", hybrid.decode_step.lower(params, sds(cache), ints, ints, jax.ShapeDtypeStruct((4,), jnp.bool_), cfg, 64))
    toks = jax.ShapeDtypeStruct((2, 16), jnp.int32); lens = jax.ShapeDtypeStruct((2,), jnp.int32)
    h(name + ".prefill_first", hybrid._prefill_first.lower(params, toks, lens, cfg))
    h(name + ".forward", hybrid.forward.lower(params, toks, cfg))
    rows = jax.eval_shape(lambda p, t, l: hybrid._prefill_first(p, t, l, cfg), params, toks, lens)[1]
    h(name + ".write_state", hybrid._write_state.lower(sds(cache), ints, ints, lens, sds(rows), lens, lens))
cfg = ModelConfig.tiny()
params = sds(jax.eval_shape(lambda k: transformer.init_params(k, cfg), jax.random.PRNGKey(0)))
kv = jax.ShapeDtypeStruct((cfg.n_layers, 4, cfg.n_kv_heads, 64, cfg.head_dim), cfg.dtype)
ints = jax.ShapeDtypeStruct((4,), jnp.int32)
h("dense.decode_step_fused", decode_step_fused.lower(params, kv, kv, ints, ints, cfg, 64))
h("dense.prefill_slots", prefill_slots.lower(params, jax.ShapeDtypeStruct((2, 16), jnp.int32), jax.ShapeDtypeStruct((2,), jnp.int32), cfg, 64))
print(json.dumps(out, indent=1))
