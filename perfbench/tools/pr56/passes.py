"""PR 56, on the chip, ONE process: the Keye prompt pass by its two kernels
against the form XLA runs in their place (`ops.dsa._chunked`), at the shapes
the check's half prompts reach: a bucket the prompt fills to its last row,
a bucket of 4,096 (under the traffic's smallest), a padded one. Logits at the
last true position, the experts chosen, the rows chosen.

    python3 perfbench/tools/pr56/passes.py
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from perfbench.lib import keye_model  # noqa: E402
from ray_tpu.models import hybrid  # noqa: E402
from ray_tpu.ops import dsa  # noqa: E402

conf = json.load(open(os.path.join(ROOT, "perfbench/configs/keye-vl-2.0-30b-a3b.1of8.json")))
cfg = keye_model.model_config(conf)
params = keye_model.make_params(cfg, 2147480311)
rng = np.random.default_rng(1)
SHAPES = [(4096, 4096), (4096, 4000), (8192, 8192), (8192, 8100), (12288, 12288)]
prompts = {b: jnp.asarray(rng.integers(1, cfg.vocab_size, (1, b)), jnp.int32)
           for b in {b for b, _ in SHAPES}}
rel = lambda x, y: float(jnp.linalg.norm((x - y).ravel()) / jnp.linalg.norm(y.ravel()))


def unpack(words, n):
    w = np.asarray(words).view(np.uint32)
    t = np.arange(n)
    return ((w[t // 32] >> (t % 32)[:, None].astype(np.uint32)) & 1).astype(bool)


got = {}
for kernels_on in (True, False):
    if not kernels_on:
        dsa.uses_prompt_kernels = lambda *a: False
        jax.clear_caches()
    for bucket, n in SHAPES:
        if not kernels_on and bucket > 8192:
            continue
        logits, rows = hybrid.prefill(params, prompts[bucket], jnp.asarray([n], jnp.int32),
                                      cfg, with_routing=True)
        got[kernels_on, bucket, n] = (np.asarray(logits[0]), np.asarray(rows["routing"]),
                                      np.asarray(rows["chosen"][:, 0]))
        del rows
        print("ran", kernels_on, bucket, n, flush=True)

for bucket, n in SHAPES:
    if (False, bucket, n) not in got:
        continue
    (la, ra, ca), (lb, rb, cb) = got[True, bucket, n], got[False, bucket, n]
    tri = np.tril(np.ones((bucket, bucket), bool))
    lines = []
    for layer in range(cfg.n_layers):
        ua, ub = unpack(ca[layer], bucket) & tri, unpack(cb[layer], bucket) & tri
        counts = ua[:n].sum(1)
        lines.append((int((ua[:n] != ub[:n]).sum()),
                      bool((counts == np.minimum(np.arange(n) + 1, 2048)).all())))
    print(json.dumps({"bucket": bucket, "true_len": n, "logits_rel": rel(jnp.asarray(la), jnp.asarray(lb)),
                      "experts_differ": int((ra[:, 0, :n] != rb[:, 0, :n]).sum()),
                      "rows_differ_and_counts_ok_by_layer": lines}), flush=True)
