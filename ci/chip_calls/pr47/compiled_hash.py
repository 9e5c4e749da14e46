"""`compiled_hash.py <checkout> <out_dir> [--described] [--layers N] [--only CELL]`: the
train step of both training cells, built from <checkout> the way the
benchmark's driver builds it (`perfbench.drivers.train_steps._program`: the
cell's configuration file, its mesh, the default optimizer), compiled for the
devices this process has (or, with --described, for a described v5e:2x2, no
chip), and hashed twice: the lowered StableHLO and the compiled
(post-optimisation) module. Left out of both hashes, because they carry the
PATH and LINE of source files and nothing of the program: `metadata={...}`,
`loc(...)`, the compiled module's debug tables (`FileNames` ...
`StackFrames`) and a Mosaic kernel's serialized body (`ops/pallas/` is compared
by `git diff`). The normalised texts go to <out_dir> for `diff`. Run it on
the parent's checkout and on this one: the `op_for_op` hashes must agree
(`op_for_op` below says what that text is); `lowered` and `compiled` differ
where the mesh is declared and in what follows from it."""
import gzip
import hashlib
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
root, out_dir = os.path.abspath(sys.argv[1]), os.path.abspath(sys.argv[2])
described = "--described" in sys.argv
layers = int(sys.argv[sys.argv.index("--layers") + 1]) if "--layers" in sys.argv else None
only = sys.argv[sys.argv.index("--only") + 1] if "--only" in sys.argv else ""
if described:
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["ALLOW_MULTIPLE_LIBTPU_LOAD"] = "1"
sys.path.insert(0, root)
import jax
import jax.numpy as jnp

from perfbench.drivers import train_steps
from ray_tpu.train.step import TrainState

if described:
    from jax.experimental import topologies

    from ray_tpu.ops.pallas import _util

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.devices = lambda *a: list(topo.devices)
    _util.on_tpu = lambda: True

STRIP = [re.compile(r", metadata=\{[^}]*\}"), re.compile(r" loc\([^\n]*"),
         re.compile(r'(\\22body\\22: \\22|"body": ?")[A-Za-z0-9+/=]*'),
         re.compile(r"^#loc[^\n]*\n", re.M),
         # the compiled module's four debug tables, each up to a blank line
         re.compile(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:.+\n)*", re.M)]


def norm(text):
    for r in STRIP:
        text = r.sub(lambda m: m.group(1) if r.groups else "", text)
    return text


def strip_braced(text, key):
    """Remove every `, key{...}` (braces balanced)."""
    out, i = [], 0
    while (j := text.find(key, i)) >= 0:
        out.append(text[i:j])
        depth, k = 1, j + len(key)
        while depth:
            depth += {"{": 1, "}": -1}.get(text[k], 0)
            k += 1
        i = k
    return "".join(out) + text[i:]


def op_for_op(compiled):
    """The compiled module less the mesh's declaration (the `xla.sdy`
    frontend attributes: the mesh's axis names and the arguments' shardings
    as the program spelled them) and with every `%name.N` renamed in order of
    first appearance: two modules that are the same instructions in the same
    order, with the same shapes, layouts, operands, replica groups and
    backend configs, give the same text whatever the counters that numbered
    them stood at."""
    text = strip_braced(compiled, ", frontend_attributes={xla.sdy")
    names = {}
    return re.sub(r"%[A-Za-z_][\w.\-]*",
                  lambda m: names.setdefault(m.group(0), f"%v{len(names)}"), text)


def sha(text):
    return hashlib.sha256(text.encode()).hexdigest()[:16]


os.makedirs(out_dir, exist_ok=True)
out = {"checkout": root, "device": f"{jax.devices()[0].device_kind} x {len(jax.devices())}"
       + (" (described, no chip)" if described else "")}
for cell, file, chips in (("mistral7b-train-1chip", "mistral-7b-v0.3.1chip.json", 1),
                          ("mistral7b-train-4chip", "mistral-7b-v0.3.4chip.json", 4)):
    if chips > len(jax.devices()) or only not in cell:
        continue
    conf = json.load(open(os.path.join(root, "perfbench", "configs", file)))
    c = {"config": conf, "chips": chips}
    cfg, mesh, opt, sh, b_sh, step_fn = train_steps._program(
        c, 2048, layers or conf["num_hidden_layers"])
    from ray_tpu.models.transformer import init_params

    params = jax.eval_shape(lambda k: init_params(k, cfg), jax.random.PRNGKey(0))
    state = jax.eval_shape(lambda p: TrainState(p, opt.init(p), jnp.zeros((), jnp.int32)), params)
    state = jax.tree_util.tree_map(
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h), state, sh)
    batch = {k: jax.ShapeDtypeStruct((2, 2048), jnp.int32, sharding=b_sh[k])
             for k in ("inputs", "targets")}
    lowered = step_fn.lower(state, batch)
    low, comp = norm(lowered.as_text()), norm(lowered.compile().as_text())
    ops = op_for_op(comp)
    for kind, text in (("lowered", low), ("compiled", comp), ("op_for_op", ops)):
        with gzip.open(os.path.join(out_dir, f"{cell}.{kind}.txt.gz"), "wt") as f:
            f.write(text)
    out[cell] = {"mesh": dict(mesh.shape), "n_layers": cfg.n_layers,
                 "lowered": sha(low), "compiled": sha(comp), "op_for_op": sha(ops),
                 "compiled_lines": comp.count("\n"),
                 "kernels": comp.count('custom_call_target="tpu_custom_call"')}
print(json.dumps(out))
